"""The control, the reference one precision step below the configuration's
put in the program's place, comes out not correct, while the program
passes: bfloat16 distances for the float32 graphs, float8 weights for the
bfloat16 model. Test sizes; the chip readings at the cells' sizes are in
PERF.md."""
import pytest

from chipbench import run as harness
from chipbench import spec
from chipbench.common import Context, with_overrides

# the tiny model's logits are ~10x smaller than the full model's, so the
# limit at this size sits between its own readings (program 0.003-0.006,
# control 0.037-0.05 on the CPU)
TINY_LOGIT_LIMIT = 0.02


def _drive(workload, seed, seconds, limits=None):
    cell = spec.load_cell(workload, harness.ROOT)
    harness.enable_cache()
    config = with_overrides(cell.config, True)
    if limits:
        config["limits"] = limits
    ctx = Context(config=config, traffic=with_overrides(cell.traffic, True),
                  seed=seed, seconds=seconds, log=lambda _m: None)
    drv = spec.system_module(cell.system)
    st = drv.setup(ctx)
    out = drv.window(ctx, st)
    drv.release(st)
    checks = {c.name: c for c in drv.check(ctx, st, out)}
    return checks, drv.control(ctx, st)


@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_sssp_bfloat16_control_fails(seed):
    checks, ctl = _drive("sssp_er10k_solo", seed, 1.0)
    assert all(c.ok for c in checks.values())
    assert ctl["dist_rel_gap"] > checks["dist_rel_gap"].limit


@pytest.mark.parametrize("seed", [5, 2**31 + 9])
def test_serve_float8_control_fails(seed):
    checks, ctl = _drive("serve_qwen3_steady", seed, 3.0,
                         limits={"logit_gap": TINY_LOGIT_LIMIT})
    assert all(c.ok for c in checks.values())
    assert ctl["logit_gap"] > TINY_LOGIT_LIMIT
