"""Device milliseconds per serve step under the program's ``mla_attn``
scope (latent attention's projections, query absorption, attention over
the latent rows, output absorption), from the traced window."""


def read(run):
    s = run.layer.get("scope_s", {}).get("mla_attn")
    return None if s is None else 1e3 * s
