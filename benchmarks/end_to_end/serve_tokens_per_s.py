"""Output tokens the client received inside the window, over its length."""


def read(result):
    return result["client"]["tokens_in_window"] / result["seconds"]
