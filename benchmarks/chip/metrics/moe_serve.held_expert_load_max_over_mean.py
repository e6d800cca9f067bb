"""The most tokens any held expert of any MoE layer took in one step,
over the mean tokens a held expert takes a step: the program's
expert-load counter, read once after a replayed cohort."""


def read(run):
    return run.layer.get("expert_load", {}).get("max_over_mean")
