"""Schedule-conformance property suite: the invariants the runtime's
first-class-backward tick lowering depends on, for EVERY builder over
randomized (N, M, V) sweeps.

The mixed F/B(/W) tick scan (``pipeline/runtime.py``) executes whatever
``schedplan.lower_to_ticks`` emits; these properties are what make that
lowering sound:

* per-(m, v) causal order on every device: F before B (before W);
* every stage-boundary edge pairs up: an op that sends has exactly one
  consumer op with the matching receive edge on the neighbouring virtual
  stage;
* the synchronous tick assignment exists (no in-flight deadlock) and its
  tick count equals the discrete-event simulator's free-comm makespan at
  unit per-op durations — the two lowerings agree on the schedule;
* the symbolic ``peak_live()`` replay equals the O(1) algebraic
  features-memory rows, and the tick lowering's residual-stash size is
  exactly that row — the runtime's memory claim is structural.

Each test is parametrized over ``schedplan.BUILDER_NAMES`` (NOT a
hand-maintained list), so a new builder is conformance-checked the
moment it is registered.
"""
try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # tier-1 must collect without hypothesis
    from _hypo_shim import given, settings, strategies as st

import pytest

from repro.core import schedplan as SP
from repro.core.simulator import simulate


def _shape(name, N, mmult, V):
    """Feasible (M, V) for a builder given the drawn knobs."""
    if name not in SP.INTERLEAVED:
        V = 1
    M = N * mmult            # M % N == 0 and M >= N: feasible for all
    return M, V


@pytest.mark.parametrize("name", SP.BUILDER_NAMES)
@settings(max_examples=15)
@given(N=st.integers(1, 6), mmult=st.integers(1, 4), V=st.integers(1, 4))
def test_f_before_b_before_w(name, N, mmult, V):
    """On every device, each (m, v)'s F precedes its B, and (zero-bubble
    plans) its B precedes its W."""
    M, v = _shape(name, N, mmult, V)
    plan = SP.build_schedule(name, M, N, v)
    for ops in plan.device_ops:
        pos = {(o.kind, o.m, o.v): i for i, o in enumerate(ops)}
        for (kind, m, vv), i in pos.items():
            if kind == "B":
                assert pos[("F", m, vv)] < i, (name, M, N, v)
            if kind == "W":
                assert pos[("B", m, vv)] < i, (name, M, N, v)


@pytest.mark.parametrize("name", SP.BUILDER_NAMES)
@settings(max_examples=15)
@given(N=st.integers(1, 6), mmult=st.integers(1, 4), V=st.integers(1, 4))
def test_send_recv_edges_pair_up(name, N, mmult, V):
    """Every send edge has exactly one matching receive edge: F(m, vs)
    sending to vs+1 pairs with F(m, vs+1) receiving from vs (backwards
    mirrored); W ops never touch the ring."""
    M, v = _shape(name, N, mmult, V)
    plan = SP.build_schedule(name, M, N, v)
    ops = [o for dev in plan.device_ops for o in dev]
    sends = {(o.kind, o.m, o.vstage, o.send_to)
             for o in ops if o.send_to is not None}
    recvs = {(o.kind, o.m, o.recv_from, o.vstage)
             for o in ops if o.recv_from is not None}
    assert sends == recvs, (name, M, N, v)
    assert all(o.send_to is None and o.recv_from is None
               for o in ops if o.kind == "W")
    # every interior edge is a single neighbour hop on the ring
    for kind, m, src, dst in sends:
        assert abs(dst - src) == 1


@pytest.mark.parametrize("name", SP.BUILDER_NAMES)
@settings(max_examples=12)
@given(N=st.integers(1, 6), mmult=st.integers(1, 4), V=st.integers(1, 4))
def test_tick_lowering_no_deadlock_and_matches_simulator(name, N, mmult, V):
    """lower_to_ticks terminates (raises on any cyclic cross-device
    dependency) and its synchronous tick count equals the discrete-event
    free-comm makespan at unit per-op durations — i.e. one tick == one
    chunk-op, with the one-tick ppermute hop hidden exactly like the
    simulator's free transfers."""
    M, v = _shape(name, N, mmult, V)
    plan = SP.build_schedule(name, M, N, v)
    lo = SP.lower_to_ticks(plan)
    ms = simulate(name, M, N, float(v),
                  float(v) * (2 if plan.has_w else 1), 0.0, V=v).makespan
    assert lo.n_ticks == pytest.approx(ms), (name, M, N, v)
    # one op per device per tick, each exactly once
    per_mv = 3 if plan.has_w else 2
    for n in range(N):
        kinds = [k for k in lo.kind[n] if k != SP.TICK_IDLE]
        assert len(kinds) == per_mv * M * v


@pytest.mark.parametrize("name", SP.BUILDER_NAMES)
@settings(max_examples=12)
@given(N=st.integers(1, 6), mmult=st.integers(1, 4), V=st.integers(1, 4))
def test_instruction_stream_matches_simulator_event_order(name, N, mmult, V):
    """The instruction lowering's slot assignment IS the discrete-event
    schedule: at unit per-op durations under free comm, every op's slot
    equals its simulated start time — so the stream runtime's
    op-completion order is exactly the simulator's event order — and the
    ring gates (``fsend``/``bsend``) fire exactly at the slots where some
    device produces a value that travels."""
    M, v = _shape(name, N, mmult, V)
    plan = SP.build_schedule(name, M, N, v)
    lo = SP.lower_to_instructions(plan)
    res = simulate(plan, M, N, float(v),
                   float(v) * (2 if plan.has_w else 1), 0.0, V=v,
                   comm="free")
    assert len(res.events) == len(lo.slot_of), (name, M, N, v)
    for (s, _e, kind, m, vs) in res.events:
        assert lo.slot_of[(kind, m, vs)] == pytest.approx(s), \
            (name, M, N, v, kind, m, vs)
    assert res.makespan == pytest.approx(lo.n_slots)
    # gates: a ring shifts at slot t iff some device SENDs on it there
    NS = N * v
    f_prod = {t for (k, _m, vs), t in lo.slot_of.items()
              if k == "F" and vs < NS - 1}
    b_prod = {t for (k, _m, vs), t in lo.slot_of.items()
              if k == "B" and vs > 0}
    assert {t for t, g in enumerate(lo.fsend) if g} == f_prod
    assert {t for t, g in enumerate(lo.bsend) if g} == b_prod
    # the point of the exercise: strictly fewer collectives than the
    # tick runtime's 2 * n_ticks (any schedule with an idle or W slot)
    assert lo.n_shifts <= 2 * lo.n_slots


@pytest.mark.parametrize("name", SP.BUILDER_NAMES)
@settings(max_examples=15)
@given(N=st.integers(1, 6), mmult=st.integers(1, 4), V=st.integers(1, 4))
def test_peak_live_replay_matches_algebraic_rows(name, N, mmult, V):
    """``SchedPlan.peak_live()`` symbolic replay == the O(1)
    ``live_activation_counts`` rows for every builder (dapple and zb-h1
    hold 1F1B's N - n window; zb-h2 the deep-warm-up/banked-W row;
    unbounded zb-auto pays M for its bubble-free steady state)."""
    M, v = _shape(name, N, mmult, V)
    plan = SP.build_schedule(name, M, N, v)
    replay = plan.peak_live()
    alg = SP.live_activation_counts(name, M, N, v)
    for r, a in zip(replay, alg):
        assert abs(r - a) <= 1, (name, M, N, v, replay, alg)


@pytest.mark.parametrize("name", SP.BUILDER_NAMES)
@settings(max_examples=12)
@given(N=st.integers(1, 6), mmult=st.integers(1, 4), V=st.integers(1, 4))
def test_residual_stash_is_the_features_row(name, N, mmult, V):
    """The tick lowering's statically allocated residual stash (``n_x``)
    equals the schedule's peak-live row — the runtime's features-memory
    footprint IS the closed form's, by register allocation."""
    M, v = _shape(name, N, mmult, V)
    plan = SP.build_schedule(name, M, N, v)
    lo = SP.lower_to_ticks(plan)
    assert lo.n_x == max(plan.peak_live()), (name, M, N, v)


@settings(max_examples=20)
@given(N=st.integers(2, 6), mmult=st.integers(1, 4))
def test_zb_h1_holds_the_1f1b_memory_window(N, mmult):
    """Acceptance (ZB-H1 is the '1F1B-equivalent memory' zero-bubble
    point): its residual window equals dapple/1f1b's N - n on every
    device, while the simulator makespan is strictly smaller."""
    M = N * mmult
    zb = SP.build_schedule("zb-h1", M, N, 1)
    da = SP.build_schedule("dapple", M, N, 1)
    assert zb.peak_live() == da.peak_live()
    ms_zb = simulate("zb-h1", M, N, 1.0, 1.0, 0.0).makespan
    ms_da = simulate("dapple", M, N, 1.0, 1.0, 0.0).makespan
    assert ms_zb < ms_da


@settings(max_examples=25)
@given(N=st.integers(1, 6), mmult=st.integers(1, 6))
def test_zb_auto_reproduces_zb_h1_under_the_1f1b_cap(N, mmult):
    """Acceptance: the automatic zero-bubble scheduler under the 1F1B
    memory cap (per-device window N - n) emits EXACTLY ZB-H1's op table
    — the hand-written schedule is a special case of the cap."""
    M = N * mmult
    cap = [max(1, min(M, N - n)) for n in range(N)]
    auto = SP.build_zb_auto(M, N, mem_limit=cap)
    h1 = SP.build_zb_h1(M, N)
    assert auto.device_ops == h1.device_ops, (M, N)


@settings(max_examples=25)
@given(N=st.integers(1, 6), mmult=st.integers(1, 6))
def test_zb_h2_is_zb_auto_under_the_h2_cap(N, mmult):
    """ZB-H2 is definitionally the automatic scheduler's table under
    :func:`schedplan.zb_h2_mem_caps` — pin the derivation, and that its
    peak-live row equals the cap exactly (the cap is attained)."""
    M = N * mmult
    h2 = SP.build_zb_h2(M, N)
    auto = SP.build_zb_auto(M, N, mem_limit=SP.zb_h2_mem_caps(M, N))
    assert h2.device_ops == auto.device_ops
    assert h2.peak_live() == SP.zb_h2_mem_caps(M, N)


@settings(max_examples=20)
@given(N=st.integers(1, 6), mmult=st.integers(2, 6))
def test_zb_h2_and_unbounded_auto_are_bubble_free_in_ticks(N, mmult):
    """Acceptance: for M >= 2N the zb-h2 table's synchronous tick count
    is exactly ``3M + N - 1`` — unit-cost M(F+B) work plus only the
    ``N - 1`` fill ramp; the entire 1F1B flush bubble is gone — and the
    unbounded zb-auto table matches it while gpipe/1f1b/zb-h1 sit
    strictly above (N > 1)."""
    M = N * mmult            # mmult >= 2 -> M >= 2N
    target = 3 * M + N - 1
    for name in ("zb-h2", "zb-auto"):
        lo = SP.lower_to_ticks(SP.build_schedule(name, M, N, 1))
        assert lo.n_ticks == target, (name, M, N, lo.n_ticks)
    if N > 1:
        h1 = SP.lower_to_ticks(SP.build_schedule("zb-h1", M, N, 1))
        assert h1.n_ticks > target


def test_dapple_table_equals_1f1b():
    """The documented 'dapple coincides with synchronous 1F1B' invariant
    is structural (the builder derives from build_1f1b) — pin it."""
    for (M, N) in ((4, 2), (8, 4), (6, 3), (5, 5)):
        da = SP.build_schedule("dapple", M, N, 1)
        fb = SP.build_schedule("1f1b", M, N, 1)
        assert da.device_ops == fb.device_ops


def test_dapple_table_is_early_backward():
    """DAPPLE's first backward on the last device comes directly after
    its first forward — M - 1 forwards earlier than gpipe's."""
    M, N = 8, 4
    da = SP.build_schedule("dapple", M, N, 1)
    gp = SP.build_schedule("gpipe", M, N, 1)
    first_b = lambda p, n: [o.kind for o in p.device_ops[n]].index("B")
    assert first_b(da, N - 1) == 1
    assert first_b(gp, N - 1) == M


def test_zb_h1_w_fills_the_drain():
    """The drain tail of every zb-h1 device alternates B, W (the W's fill
    what would otherwise be bubbles), ending on the last W."""
    plan = SP.build_schedule("zb-h1", 8, 4, 1)
    for n, ops in enumerate(plan.device_ops):
        tail = [o.kind for o in ops[-2 * (4 - n):]]
        assert tail == ["B", "W"] * (4 - n), (n, tail)


def test_zb_h2_has_double_warmup_and_banked_drain_ws():
    """ZB-H2's structure: device n warms up with ``2(N-n) - 1`` forwards
    (double 1F1B's depth) and the downstream devices end in a run of
    banked W ops that fills the drain."""
    M, N = 12, 4
    plan = SP.build_schedule("zb-h2", M, N, 1)
    for n, ops in enumerate(plan.device_ops):
        kinds = [o.kind for o in ops]
        assert kinds.index("B") == 2 * (N - n) - 1, (n, kinds)
    # the last device banks the deepest W backlog: a strictly longer
    # trailing all-W run than device 0's
    ws = [0] * N
    for n in range(N):
        k = [o.kind for o in plan.device_ops[n]]
        t = 0
        while k and k[-1] == "W":
            k.pop(); t += 1
        ws[n] = t
    assert ws[N - 1] > ws[0], ws


def test_build_schedule_mem_limit_only_for_zb_auto():
    """The mem_limit knob belongs to the automatic scheduler alone."""
    with pytest.raises(ValueError, match="mem_limit"):
        SP.build_schedule("zb-h1", 4, 2, 1, mem_limit=3)
    plan = SP.build_schedule("zb-auto", 4, 2, 1, mem_limit=2)
    assert max(plan.peak_live()) <= 2


@pytest.mark.parametrize("name", SP.BUILDER_NAMES)
@pytest.mark.parametrize("lowering", ["ticks", "stream", "grad_sync"])
@settings(max_examples=10)
@given(N=st.integers(1, 6), mmult=st.integers(1, 4), V=st.integers(1, 4))
def test_split_head_tables(name, lowering, N, mmult, V):
    """The split head's per-tick tables mark exactly one tick per
    micro-batch, the last device's F of the last virtual stage; the dy
    slot written there is the one that micro-batch's B_SEED reads, and
    the stash holds as many slots as dy values are ever alive at once —
    for the tick lowering, the instruction streams and a plan with AR
    ops alike."""
    M, v = _shape(name, N, mmult, V)
    plan = SP.build_schedule(name, M, N, v,
                             grad_sync=lowering == "grad_sync")
    lo = (SP.lower_to_instructions(plan).ticks if lowering == "stream"
          else SP.lower_to_ticks(plan))
    last = N - 1
    head_ticks = [t for t in range(lo.n_ticks) if lo.head[t]]
    assert sorted(lo.head_m[t] for t in head_ticks) == list(range(M))
    alive = [0] * lo.n_ticks
    for t in head_ticks:
        m = lo.head_m[t]
        assert lo.kind[last][t] == SP.TICK_F and lo.m[last][t] == m
        assert lo.v[last][t] == v - 1
        seeds = [u for u in range(lo.n_ticks)
                 if lo.kind[last][u] == SP.TICK_B_SEED
                 and lo.m[last][u] == m]
        assert len(seeds) == 1 and seeds[0] > t
        assert lo.dyr[last][seeds[0]] == lo.dyw[t]
        for u in range(t, seeds[0] + 1):
            alive[u] += 1
    for t in range(lo.n_ticks):
        if not lo.head[t]:
            assert lo.head_m[t] == -1 and lo.dyw[t] == -1
    assert lo.n_dy == max(alive)
    # only the last device seeds, and only its seeds read the stash
    for n in range(N):
        for t in range(lo.n_ticks):
            seed = lo.kind[n][t] == SP.TICK_B_SEED
            assert seed <= (n == last)
            assert (lo.dyr[n][t] >= 0) == seed


@pytest.mark.parametrize("name,n_dy", [("1f1b", 1), ("dapple", 1),
                                       ("zb-h1", 1), ("gpipe", 8)])
def test_split_head_stash_sizes(name, n_dy):
    """1F1B's seed B follows its head at once (one dy slot); GPipe runs
    every forward first (M slots)."""
    assert SP.lower_to_ticks(SP.build_schedule(name, 8, 4)).n_dy == n_dy
