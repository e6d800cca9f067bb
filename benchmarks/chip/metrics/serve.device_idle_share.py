"""Share of the traced stretch in which no operation ran on the device
(1 minus the union of device op intervals), in percent."""


def read(run):
    share = run.trace.get("idle_share")
    return None if share is None else 100.0 * share
