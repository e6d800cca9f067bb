"""Host milliseconds per engine round: each round's wall time minus the
time inside the serve-step call, summed over the window, per round."""


def read(run):
    rounds = run.layer.get("rounds")
    return 1e3 * run.layer["host_s"] / rounds if rounds else None
