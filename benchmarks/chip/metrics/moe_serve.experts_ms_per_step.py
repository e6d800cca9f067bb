"""Device milliseconds per serve step under the program's ``moe_experts``
scope (the held routed experts' products), from the traced window."""


def read(run):
    s = run.layer.get("scope_s", {}).get("moe_experts")
    return None if s is None else 1e3 * s
