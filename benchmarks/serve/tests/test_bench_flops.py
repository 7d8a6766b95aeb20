"""The operation and byte counts, against the ``quant_matmul_int`` calls
that the program's own jitted steps make (their jaxpr, at a small size)."""
import collections
import os
import sys

import jax
import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE),
                os.path.join(os.path.dirname(os.path.dirname(
                    os.path.dirname(HERE))), "src")]

from servebench import flops  # noqa: E402
from servebench.window import StepRecord  # noqa: E402

B, BUCKET = 4, 16


def _calls(jaxpr, mult=1, out=None):
    """(M, K, N) -> calls of the ``quant_matmul_int`` jit, and the count of
    ``pallas_call``s, through nested jaxprs (a scan multiplies)."""
    out = out if out is not None else (collections.Counter(), [0])
    for eqn in jaxpr.eqns:
        name = eqn.params.get("name")
        if eqn.primitive.name == "pallas_call":
            out[1][0] += mult
        if name == "quant_matmul_int":
            (m, k), (_, n) = (v.aval.shape for v in eqn.invars[:2])
            out[0][(m, k, n)] += mult
        inner = mult * eqn.params.get("length", 1) \
            if eqn.primitive.name == "scan" else mult
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                j = getattr(sub, "jaxpr", sub)
                if hasattr(j, "eqns"):
                    _calls(j, inner, out)
    return out


@pytest.fixture(scope="module", params=["qwen1.5-0.5b", "h2o-danube-1.8b"])
def cfg(request):
    import repro.configs as configs

    return configs.get(request.param).smoke().scaled(
        pim_mode="quant", n_layers=3, max_seq_len=64)


def test_decode_step_calls(cfg):
    from repro.models import model_lib as M
    from repro.models.layers import as_shapes

    params = as_shapes(M.param_specs(cfg))
    nb = B * -(-cfg.max_seq_len // 8) + 1
    caches = M.paged_cache_specs(cfg, B, cfg.max_seq_len, nb, 8)
    bps = cfg.window_ring_blocks(8) or -(-cfg.max_seq_len // 8)
    jaxpr = jax.make_jaxpr(
        lambda p, t, pos, a, c, tb: M.decode_step_slots(
            p, t, pos, a, c, cfg, block_tables=tb))(
        params, jax.ShapeDtypeStruct((B, 1), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.bool_), caches,
        jax.ShapeDtypeStruct((B, bps), jnp.int32))
    found, pallas = _calls(jaxpr.jaxpr)
    want = flops.kernel_calls([StepRecord(0, 1, [], decode_rows=2)], cfg, B,
                              BUCKET)
    assert dict(found) == want
    assert pallas[0] == sum(want.values())


def test_prefill_calls(cfg):
    from repro.models import model_lib as M
    from repro.models.layers import as_shapes

    params = as_shapes(M.param_specs(cfg))
    # a 20-token prompt pads to the 32-token bucket; past a 16-token
    # window it runs unpadded
    rows = flops.bucket(20, BUCKET, cfg.sliding_window)
    assert rows == (20 if cfg.sliding_window else 32)
    jaxpr = jax.make_jaxpr(lambda p, t, li: M.prefill(
        p, {"tokens": t}, cfg, last_index=li))(
        params, jax.ShapeDtypeStruct((1, rows), jnp.int32),
        jax.ShapeDtypeStruct((1,), jnp.int32))
    found, _ = _calls(jaxpr.jaxpr)
    want = flops.kernel_calls([StepRecord(0, 1, [20], decode_rows=0)], cfg,
                              B, BUCKET)
    assert dict(found) == want


def test_call_cost_and_roofline():
    ops, nbytes = flops.call_cost(32, 2560, 6912)
    assert ops == 2 * 32 * 2560 * 6912
    assert nbytes == 32 * 2560 + 2560 * 6912 + 4 * 32 * 6912
    pk = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}
    least, by_ops, by_bytes = flops.roofline_seconds(
        {(32, 2560, 6912): 10, (4096, 2560, 6912): 1}, pk)
    assert by_bytes > by_ops * 0.1
    assert least == pytest.approx(
        10 * nbytes / 819e9 + 2 * 4096 * 2560 * 6912 / 393e12)


def test_model_ops_counts_real_tokens():
    import repro.configs as configs

    cfg = configs.get("h2o-danube-1.8b")
    lin = 2 * sum(k * n for k, n in flops.linear_shapes(cfg)) * 24
    # per layer: q and o 2560^2, k and v 2560 x 640, three 2560 x 6912
    assert lin / 2 == 24 * (2 * 2560 ** 2 + 2 * 2560 * 640 + 3 * 2560 * 6912)
    steps = [StepRecord(0, 1, [5000], decode_rows=0),
             StepRecord(1, 2, [], decode_rows=3, decode_ctx=3 * 4096)]
    int8, other = flops.model_ops(steps, cfg)
    assert int8 == lin * 5003
    attn = 4 * 32 * 80 * 24
    ctx = 4096 * 4097 // 2 + (5000 - 4096) * 4096    # window-clipped
    head = 2 * 2560 * 32000
    assert other == attn * (ctx + 3 * 4096) + head * 4
    assert flops.prompt_ctx(10, None) == 55
    assert flops.prompt_ctx(10, 4) == 4 * 5 // 2 + 6 * 4


def test_peaks_are_keyed_by_device_kind():
    assert flops.peaks("TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError):
        flops.peaks("cpu")


@pytest.mark.parametrize("r", [1, 2, 4, 8])
def test_tile_follows_the_programs_split(r):
    from repro.kernels.quant_matmul.tp import tp_tile_shape

    dims = [1, 2, 3, 4, 6, 8, 64, 80, 128, 640, 1024, 2560, 2816, 6144,
            6912, 24576, 151936]
    for k in dims:
        for n in dims:
            want = (k, n) if r == 1 else tp_tile_shape((k, n), r)
            assert flops.tile(k, n, r) == want, (k, n, r)


def test_one_chip_counts_are_unchanged():
    import repro.configs as configs

    cfg = configs.get("h2o-danube-1.8b")
    steps = [StepRecord(0, 1, [100, 700], decode_rows=0),
             StepRecord(1, 2, [], decode_rows=5)]
    want = {}
    for m, n_calls in ((128, 1), (704, 1), (32, 1)):
        for k, n in flops.linear_shapes(cfg):
            want[(m, k, n)] = want.get((m, k, n), 0) + n_calls * 24
    assert flops.kernel_calls(steps, cfg, 32, 64) == want
    assert flops.kernel_calls(steps, cfg, 32, 64, r=1) == want


def test_four_chips_count_each_chips_tile():
    import repro.configs as configs

    cfg = configs.get("h2o-danube-1.8b")
    got = flops.kernel_calls([StepRecord(0, 1, [], decode_rows=5)], cfg, 32,
                             64, r=4)
    # q and o (2560x2560, a tie) and the MLP's 2560x6912 split their
    # columns; k and v (2560x640) and the 6912x2560 down projection their
    # rows
    assert got == {(32, 2560, 640): 4 * 24 * 2, (32, 640, 640): 4 * 24 * 2,
                   (32, 2560, 1728): 4 * 24 * 2, (32, 1728, 2560): 4 * 24}
    one = flops.kernel_calls([StepRecord(0, 1, [], decode_rows=5)], cfg, 32,
                             64)
    ops = sum(c * flops.call_cost(*k)[0] for k, c in got.items())
    assert ops == sum(c * flops.call_cost(*k)[0] for k, c in one.items())


@pytest.mark.parametrize("chips", [1, 4])
def test_mfu_is_over_all_the_cells_chips(chips):
    import repro.configs as configs
    from servebench import run, spec
    from servebench.window import RunRecord

    class Cell:
        pass

    cell = Cell()
    cell.chips = chips
    cfg = configs.get("qwen1.5-0.5b")
    rec = RunRecord(open=0.0, close=10.0)
    rec.steps = [StepRecord(0.0, 0.5, [300], decode_rows=0),
                 StepRecord(0.5, 0.6, [], decode_rows=12, decode_ctx=3600)]
    pk = flops.peaks("TPU v5 lite")
    ctx = run.Context(cell=cell, cfg=cfg, scfg=None, run=rec, setup_s=1.0,
                      admits={}, trace=None, peaks=pk)
    got = spec.Layout().metric_reader("mfu.chat")(ctx, "mfu.chat")
    int8, other = flops.model_ops(rec.steps, cfg)
    one_chip = 100.0 * (int8 / pk["int8_ops_per_s"]
                        + other / pk["bf16_flops_per_s"]) / 0.6
    assert got == (one_chip if chips == 1 else pytest.approx(one_chip / 4))
