"""Helper: the training launcher on a (2, 2) mesh, smoke config in f32,
in lci_dedicated and bsp mode.  The train state must be created sharded
(no device holds it whole), and the two modes must agree on the first
step from the same seed: the loss (forward collectives) and the gradient
norm after the gradient exchange.  In f32 they differ only by reduction
order.  Run with 4 fake devices."""
import jax.numpy as jnp

import repro.launch.train as train_launch

first = {}
for mode in ("lci_dedicated", "bsp"):
    res = train_launch.train("olmo-1b", smoke=True, dtype=jnp.float32,
                             steps=3, seq=32, batch=4, mesh="2x2", mode=mode)
    per_dev = res.state_bytes
    total = sum(per_dev.values())
    assert len(per_dev) == 4, per_dev
    assert max(per_dev.values()) <= total / 2, per_dev
    losses = [h["loss"] for h in res.history]
    assert len(losses) == 3 and all(l == l for l in losses), losses
    first[mode] = res.history[0]
lci, bsp = first["lci_dedicated"], first["bsp"]
assert abs(lci["loss"] - bsp["loss"]) < 1e-5, first
assert abs(lci["grad_norm"] - bsp["grad_norm"]) < 1e-4 * bsp["grad_norm"], first
print("HELPER-OK")
