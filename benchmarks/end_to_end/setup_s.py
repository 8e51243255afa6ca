"""Process start to the opening of the window: cluster, replica or trainer,
weights, compilation or cache reads, the check, warm-up and pre-roll."""


def read(result):
    return result["clock"]["setup_s"]
