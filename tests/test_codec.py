"""Control codec round-trips, including property-based random legal ops.

The property tests run under ``hypothesis`` (pinned in
requirements-ci.txt).  Strategies deliberately cover the codable space
edge-to-edge: all five gate types, arbitrary (non-power-of-two) periods
up to ``k - 1``, range inits spanning partitions, and standard-model
arbitrary partition subsets.
"""
import pytest
from hypothesis import assume, given, settings, strategies as st

from repro.core import (GateOp, InitOp, Operation, PartitionConfig, decode,
                        encode, message_bits, validate)

CFG = PartitionConfig(1024, 32)

TWO_INPUT_GATES = ["NOR", "OR", "NAND", "AND"]


def _roundtrip(op, model, gate_type):
    msg = encode(op, CFG, model)
    # frame adds 2 bits on top of the paper-counted payload
    assert len(msg) == message_bits(model, CFG) + 2
    back = decode(msg, CFG, model, gate_type)
    if op.is_init:
        assert set(back.init.columns(CFG)) == set(op.init.columns(CFG))
    else:
        assert {(g.gate, g.inputs, g.output) for g in back.gates} == \
            {(g.gate, g.inputs, g.output) for g in op.gates}


def test_serial_roundtrip_all_models():
    op = Operation(gates=(GateOp("NOR", (5, 700), 900),))
    _roundtrip(op, "baseline", "NOR")
    _roundtrip(op, "unlimited", "NOR")
    op2 = Operation(gates=(GateOp("NOR", (CFG.col(3, 1), CFG.col(3, 7)),
                                  CFG.col(9, 2)),))
    for model in ("standard", "minimal"):
        _roundtrip(op2, model, "NOR")


def test_split_input_roundtrip_unlimited_only():
    op = Operation(gates=(GateOp("NOR", (CFG.col(0, 4), CFG.col(2, 9)),
                                 CFG.col(5, 1)),))
    _roundtrip(op, "unlimited", "NOR")


@pytest.mark.slow
@given(
    intra=st.tuples(st.integers(0, 31), st.integers(0, 31),
                    st.integers(0, 31)).filter(
        lambda t: len({t[0], t[1]}) == 2 and t[2] not in t[:2]),
    period=st.integers(1, 31),        # arbitrary, not just powers of two
    start=st.integers(0, 31),
    gate=st.sampled_from(TWO_INPUT_GATES),
)
@settings(max_examples=40, deadline=None)
def test_parallel_periodic_roundtrip(intra, period, start, gate):
    """Random within-partition periodic ops are legal + codable everywhere,
    for every two-input gate type (the type rides out-of-band)."""
    ia, ib, io = intra
    parts = list(range(start, CFG.k, period))
    op = Operation(gates=tuple(
        GateOp(gate, (CFG.col(p, ia), CFG.col(p, ib)), CFG.col(p, io))
        for p in parts))
    for model in ("unlimited", "standard", "minimal"):
        validate(op, CFG, model)
        _roundtrip(op, model, gate)


@pytest.mark.slow
@given(
    dist=st.integers(1, 15),
    extra=st.integers(1, 16),
    start=st.integers(0, 15),
    forward=st.booleans(),
    intra=st.tuples(st.integers(0, 31), st.integers(0, 31)),
)
@settings(max_examples=40, deadline=None)
def test_semiparallel_periodic_roundtrip(dist, extra, start, forward, intra):
    """Random uniform-distance periodic copy ops round-trip in every model."""
    period = dist + extra                  # minimal needs T > distance
    assume(period <= CFG.k - 1)            # ... and T encodable in log2(k)
    direction = 1 if forward else -1
    src_intra, dst_intra = intra
    gates = []
    p = start
    while 0 <= p + direction * dist < CFG.k and p < CFG.k:
        gates.append(GateOp("NOT", (CFG.col(p, src_intra),),
                            CFG.col(p + direction * dist, dst_intra)))
        p += period
    if not gates:
        return
    op = Operation(gates=tuple(gates))
    for model in ("unlimited", "standard", "minimal"):
        validate(op, CFG, model)
        _roundtrip(op, model, "NOT")


@st.composite
def _range_inits(draw):
    """Arbitrary in-bounds [lo, hi] range inits (dependent draw)."""
    lo = draw(st.integers(0, CFG.n - 1))
    hi = draw(st.integers(lo, CFG.n - 1))
    return InitOp("range", lo, hi)


@pytest.mark.slow
@given(init=_range_inits())
@settings(max_examples=40, deadline=None)
def test_random_range_init_roundtrip(init):
    """Random range inits round-trip wherever they are encodable: every
    model for in-partition ranges; minimal only when the span ends at the
    last partition (its generator has no end-partition field)."""
    p_lo, p_hi = CFG.partition(init.lo), CFG.partition(init.hi)
    models = ["baseline", "unlimited", "standard"]
    if p_lo == p_hi or p_hi == CFG.k - 1:
        models.append("minimal")
    for model in models:
        _roundtrip(Operation(init=init), model, "INIT")


@st.composite
def _periodic_inits(draw):
    ilo = draw(st.integers(0, CFG.m - 1))
    ihi = draw(st.integers(ilo, CFG.m - 1))
    p_start = draw(st.integers(0, CFG.k - 1))
    p_end = draw(st.integers(p_start, CFG.k - 1))
    period = draw(st.integers(1, CFG.k - 1))
    return InitOp("periodic", ilo, ihi, p_start, p_end, period)


@pytest.mark.slow
@given(init=_periodic_inits())
@settings(max_examples=40, deadline=None)
def test_random_periodic_init_roundtrip(init):
    """Random periodic inits (any stride, any partition window) round-trip
    in every partition model."""
    for model in ("unlimited", "standard", "minimal"):
        validate(Operation(init=init), CFG, model)
        _roundtrip(Operation(init=init), model, "INIT")


@pytest.mark.slow
@given(
    parts=st.lists(st.integers(0, 31), min_size=1, max_size=10),
    intra=st.tuples(st.integers(0, 31), st.integers(0, 31),
                    st.integers(0, 31)),
)
@settings(max_examples=40, deadline=None)
def test_standard_arbitrary_partition_subsets(parts, intra):
    """The standard model's per-partition enable bits encode *any* set of
    active partitions, periodic or not — only minimal requires the
    uniform stride its range generator can reproduce."""
    ia, ib, io = intra
    assume(ia != ib and io not in (ia, ib))
    parts = sorted(set(parts))
    op = Operation(gates=tuple(
        GateOp("NOR", (CFG.col(p, ia), CFG.col(p, ib)), CFG.col(p, io))
        for p in parts))
    for model in ("unlimited", "standard"):
        validate(op, CFG, model)
        _roundtrip(op, model, "NOR")


def test_init_roundtrips():
    for model in ("baseline", "unlimited", "standard", "minimal"):
        _roundtrip(Operation(init=InitOp("range", 40, 50)), model, "INIT")
    for model in ("unlimited", "standard", "minimal"):
        _roundtrip(Operation(init=InitOp("periodic", 3, 9, 0, 28, 4)),
                   model, "INIT")
    # spanning range init: standard encodes arbitrary end partitions
    _roundtrip(Operation(init=InitOp("range", 10, 200)), "standard", "INIT")


def test_illegal_op_refused_by_encoder():
    op = Operation(gates=(GateOp("NOR", (CFG.col(0, 0), CFG.col(1, 0)),
                                 CFG.col(2, 0)),))
    with pytest.raises(Exception):
        encode(op, CFG, "minimal")
