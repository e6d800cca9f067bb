"""Distributed correctness (subprocess: 8 fake devices, (2,4) mesh).

The heavyweight guarantees of the framework:
* every family's shard_map loss == local loss (BSP and LCI modes);
* grad_sync'd distributed gradients == single-device gradients;
* the sharded serve step decodes the local oracle's tokens and writes
  its K/V rows, for dense, GQA parallel-block, SSM and MoE models, with
  the cache seq-sharded over model or, at batch 1, over data and model.
"""
import pytest


@pytest.mark.slow
def test_all_families_distributed_equivalence(helper_runner):
    out = helper_runner("dist_equivalence", devices=8, timeout=1500)
    assert out.count("OK loss") >= 16       # 8 configs x 2 modes
    assert out.count("OK grad") >= 8        # grad-checked configs x 2


@pytest.mark.slow
def test_sharded_decode_matches_oracle(helper_runner):
    """The serve step on a (data, model) mesh, FSDP weights gathered per
    layer, decodes the local oracle's tokens and writes its K/V rows."""
    out = helper_runner("sharded_decode", devices=8, timeout=1200)
    assert out.count("sharded=1.000") >= 4
