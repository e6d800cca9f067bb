#!/usr/bin/env python3
"""Chip smoke test: the main path, end to end, on a TPU.

    python chip_smoke.py              # one chip: olmo-1b serving + reference
    python chip_smoke.py --chips 4    # four chips: olmo-1b training, 2x2 mesh

One chip (the default) runs two phases in this process:

* **serve** — olmo-1b at its published widths (random bf16 weights from
  a seed) behind the continuous-batching scheduler, through
  ``repro.launch.serve`` with ``--transport``: prompts ride the prefill
  endpoint, generated tokens the decode endpoint, and the jitted serve
  step decodes on the chip.  Every request must be delivered exactly
  once with ``max_new`` tokens.
* **reference** — the same weights: the serve step teacher-forced over
  ``REF_LEN`` positions must pick greedy tokens the model's full forward
  pass also ranks at the top (``repro.serving.agreement``: near-top
  agreement with the forward pass, a context-free control that must
  stay low; ``tests/test_agreement.py`` plants a dropped cache write and
  a late query rotation and sees the check fail).

``--chips 4`` runs only the training phase: a few olmo-1b steps at full
width on a (data=2, model=2) mesh through ``repro.launch.train``, the
train state created sharded, in ``lci_dedicated`` mode and again in
``bsp`` mode from the same seed, and ``bsp`` once more as a witness of
run-to-run spread; the loss must fall in every run and the first-step
losses agree within ``LOSS_ATOL``.  bf16 rounding then separates the
modes (the gaps are printed), so one more step per mode in f32, where
they differ by reduction order only, checks the gradient exchange: the
first loss within ``F32_LOSS_ATOL`` and the gradient norm after the
exchange within ``F32_GRAD_RTOL``.

The last line printed is one JSON object naming the device.  Any failed
phase exits nonzero and prints no such line; a run that finds no TPU
fails before any phase.
"""
import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.abspath(__file__))

ARCH = "olmo-1b"
REF_LEN, REF_BATCH = 32, 2
LOSS_ATOL = 0.05        # lci_dedicated vs bsp first-step loss, bf16 (nats)
F32_LOSS_ATOL = 1e-3    # the same in f32 (nats)
F32_GRAD_RTOL = 1e-3    # first-step grad norm in f32 (relative)


def fail(msg: str) -> None:
    print(f"[chip_smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def log(kind: str, msg: str) -> None:
    print(f"[chip_smoke] [{kind}] {msg}", flush=True)


def phase_serve(kind, cfg, params):
    from repro.launch.serve import serve
    requests, max_new = 16, 16
    res = serve(cfg, params, requests=requests, max_new=max_new,
                max_batch=8, cache_len=512, transport=True)
    rids = [rid for rid, _ in res.received]
    log(kind, f"serve: {len(set(rids))}/{requests} requests completed, "
              f"{res.n_tokens} tokens delivered over the decode endpoint")
    log(kind, f"serve: prefill endpoint posts per device "
              f"{res.prefill_posts}")
    log(kind, f"serve: compile {res.compile_s:.2f}s, wall "
              f"{res.wall_s:.2f}s, {res.engine_rounds} engine rounds")
    if sorted(rids) != sorted(res.submitted):
        fail(f"delivery not exactly-once: submitted {sorted(res.submitted)}"
             f", received {sorted(rids)}")
    short = [(rid, len(t)) for rid, t in res.received if len(t) != max_new]
    if short:
        fail(f"requests delivered with the wrong token count: {short}")


def phase_reference(kind, cfg, model, params):
    from repro.serving import agreement as ag
    res = ag.decode_agreement(cfg, model, params, length=REF_LEN,
                              batch=REF_BATCH)
    log(kind, f"reference: serve-step vs full forward over {REF_LEN}x"
              f"{REF_BATCH} positions: exact top-1 {res.top1:.4f}; within "
              f"{ag.NEAR_TOP} logit std of the top {res.agree:.4f} (need >= "
              f"{ag.AGREE_MIN}); context-free control {res.control:.4f} "
              f"(need <= {ag.CONTROL_MAX})")
    for msg in res.failures():
        fail(msg)


def phase_train(kind):
    import jax
    import jax.numpy as jnp

    from repro.launch.train import train

    runs = {}
    # at the config's bf16: 5 steps per mode, bsp twice (the repeat from
    # the same seed is the run-to-run spread); then one f32 step per mode,
    # where the modes differ by reduction order only
    for label, mode, dtype, steps in (
            ("lci_dedicated", "lci_dedicated", None, 5),
            ("bsp", "bsp", None, 5),
            ("bsp-repeat", "bsp", None, 5),
            ("lci_dedicated-f32", "lci_dedicated", jnp.float32, 1),
            ("bsp-f32", "bsp", jnp.float32, 1)):
        res = train(ARCH, dtype=dtype, steps=steps, seq=256, batch=8,
                    lr=3e-3, warmup=1, mesh="2x2", mode=mode)
        for dev, stats in res.memory.items():
            log(kind, f"train[{label}]: {dev} holds "
                      f"{res.state_bytes.get(dev, 0) / 2**30:.3f} GiB of "
                      f"train state; bytes_in_use "
                      f"{stats.get('bytes_in_use', 0) / 2**30:.3f} GiB")
        losses = [h["loss"] for h in res.history]
        gnorms = [h["grad_norm"] for h in res.history]
        log(kind, f"train[{label}]: losses {losses}, grad norms {gnorms}, "
                  f"wall {res.wall_s:.2f}s")
        if len(res.state_bytes) != len(jax.devices()):
            fail(f"train state on {len(res.state_bytes)} devices, "
                 f"not on all {len(jax.devices())}")
        state_total = sum(res.state_bytes.values())
        if max(res.state_bytes.values()) > state_total / 2:
            fail(f"one device holds more than half the train state: "
                 f"{res.state_bytes}")
        if steps > 1 and not losses[-1] < losses[0]:
            fail(f"loss did not fall in {label}: {losses}")
        runs[label] = (losses, gnorms)

    def gaps(a, b):
        return [abs(x - y) for x, y in zip(runs[a][0], runs[b][0])]

    log(kind, f"train: per-step loss |diff| lci_dedicated vs bsp "
              f"{gaps('lci_dedicated', 'bsp')}; bsp vs bsp-repeat "
              f"{gaps('bsp', 'bsp-repeat')}")
    checks = (("bf16", "lci_dedicated", "bsp", LOSS_ATOL, None),
              ("f32", "lci_dedicated-f32", "bsp-f32", F32_LOSS_ATOL,
               F32_GRAD_RTOL))
    for name, a, b, loss_tol, grad_tol in checks:
        (la, ga), (lb, gb) = runs[a], runs[b]
        loss_diff = abs(la[0] - lb[0])
        grad_rel = abs(ga[0] - gb[0]) / gb[0]
        log(kind, f"train: first step {name}, lci_dedicated vs bsp: loss "
                  f"{la[0]:.6f} vs {lb[0]:.6f}, |diff| {loss_diff:.6g} "
                  f"(need <= {loss_tol}); grad norm after the gradient "
                  f"exchange {ga[0]:.6f} vs {gb[0]:.6f}, relative diff "
                  f"{grad_rel:.6g}"
                  + (f" (need <= {grad_tol})" if grad_tol else ""))
        if loss_diff > loss_tol:
            fail(f"{name}: lci_dedicated and bsp disagree on the first "
                 f"loss: {loss_diff}")
        if grad_tol is not None and grad_rel > grad_tol:
            fail(f"{name}: lci_dedicated and bsp disagree on the first "
                 f"synced gradient's norm: relative {grad_rel}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serving + reference; 4: mesh training only")
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(REPO, "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        fail(f"the repro package is not next to this script: {e}")
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: jax found {len(devices)} {dev.platform} device(s); "
             f"this script has no CPU path")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} but jax sees {len(devices)} device(s)")
    kind = dev.device_kind

    from repro.launch.compile_cache import enable_compile_cache
    log(kind, f"{len(devices)} device(s); compile cache at "
              f"{enable_compile_cache()}")

    if args.chips == 4:
        phase_train(kind)
    else:
        from repro.launch.serve import build
        cfg, model, params = build(ARCH)
        phase_serve(kind, cfg, params)
        phase_reference(kind, cfg, model, params)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": kind, "count": len(devices)}}))


if __name__ == "__main__":
    main()
