"""runtime: ``JaxTrainer.fit()`` start to the end of the warm steps."""


def read(result):
    return result["clock"].get("trainer_first_step_s")
