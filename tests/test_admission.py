"""Deadline-aware admission control (serving/admission.py): policy/verdict
surfaces, the slack cost model, composer reorder/shed, arena block-table
parking, preempt→resume bit-identity against a FIFO oracle, and a property
test that random overload interleavings never corrupt another slot's
decode output.  The fifo baseline must stay byte-inert."""
import os

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.allocator import ParallelPlan
from repro.core.categories import (REJECT_VERDICTS, Outcome, Sensitivity,
                                   TaskCategory)
from repro.models import transformer as T
from repro.serving.admission import (ADMISSION_POLICIES,
                                     AdmissionController, ParkedEntry)
from repro.serving.arena import KVArena
from repro.serving.batching import BSComposer, MFComposer, QueuedItem
from repro.serving.engine import GenerationRequest, ServiceRuntime

from conftest import toy_config

LAT = TaskCategory(Sensitivity.LATENCY, False)
FREQ = TaskCategory(Sensitivity.FREQUENCY, False)


def _plan(bs=2, **kw):
    return ParallelPlan(service="t", category=LAT, bs=bs, **kw)


@pytest.fixture(scope="module")
def toy():
    cfg = toy_config()
    return cfg, T.init(jax.random.PRNGKey(0), cfg)


def _req(rid, max_new=4, deadline=0.0, prompt=4, stream=0):
    return GenerationRequest(
        rid=rid, tokens=np.arange(1, 1 + prompt, dtype=np.int32),
        max_new_tokens=max_new, deadline_s=deadline, stream=stream)


def _drain(rt, t, results, rejects, limit=2000.0):
    preempted = resumed = 0
    while rt.pending() or rt.in_flight():
        st_ = rt.step(now=t)
        results += st_.results
        rejects += st_.rejected
        preempted += st_.preempted
        resumed += st_.resumed
        t += 1.0
        assert t < limit, "engine failed to drain"
    return t, preempted, resumed


# ---------------------------------------------------------------------------
# controller unit surface (stub runtime — no engine, no jax)
# ---------------------------------------------------------------------------

class _StubRuntime:
    def __init__(self, slots=2, policy="sdf"):
        self.plan = _plan(bs=slots, admission=policy)
        self.composer = BSComposer(self.plan)
        self.prefill_chunk_tokens = 4
        self._slots = slots

    def total_slots(self):
        return self._slots


def test_policy_knob_validated():
    assert ADMISSION_POLICIES == ("fifo", "sdf")
    with pytest.raises(ValueError, match="admission policy"):
        AdmissionController(_StubRuntime(policy="edf"))
    # plan knob drives the default; fifo is inert
    ctrl = AdmissionController(_StubRuntime(policy="fifo"))
    assert not ctrl.active
    assert AdmissionController(_StubRuntime(policy="sdf")).active


def test_reject_verdicts_enum():
    assert set(REJECT_VERDICTS) == {Outcome.DEADLINE_MISSED,
                                    Outcome.CONGESTION, Outcome.OFFLOAD,
                                    Outcome.FAILED}
    assert Outcome.ADMIT not in REJECT_VERDICTS
    assert Outcome("deadline_missed") is Outcome.DEADLINE_MISSED


def test_cold_controller_admits_like_fifo():
    """Before any completion the EWMAs are 0: every estimate collapses to
    free, so only an already-expired deadline can shed."""
    ctrl = AdmissionController(_StubRuntime())
    live = _req(0, deadline=10.0)
    dead = _req(1, deadline=3.0)
    for rid, req in ((0, live), (1, dead)):
        ctrl.rt.composer.add(QueuedItem(payload=req, rid=rid))
    assert ctrl.service_estimate(live) == 0.0
    assert ctrl.wait_estimate(now=5.0) == 0.0
    dropped = ctrl.shed(now=5.0)
    assert [(it.rid, v) for it, v in dropped] == \
        [(1, Outcome.DEADLINE_MISSED)]
    assert ctrl.verdicts == {"deadline_missed": 1}


def test_cost_model_learns_caller_clock():
    ctrl = AdmissionController(_StubRuntime())
    for t in (0.0, 2.0, 4.0):
        ctrl.note_step(t)
    assert ctrl._round_dt == pytest.approx(2.0)

    class _Res:
        admitted_s, finished_s = 1.0, 11.0
    ctrl.observe(_Res())
    assert ctrl._svc_logical == pytest.approx(10.0)
    # 4 decode rounds + ceil(4/4) prefill chunk = 5 rounds of 2.0 each
    assert ctrl.service_estimate(_req(0)) == pytest.approx(10.0)
    assert ctrl.slack(_req(0, deadline=30.0), now=5.0) == pytest.approx(15.0)
    assert ctrl.slack(_req(0), now=5.0) == float("inf")
    # position-aware wait: head takes the next slot-turn, not the queue
    assert ctrl.wait_estimate(0.0, position=0) == pytest.approx(5.0)
    assert ctrl.wait_estimate(0.0, position=3) == pytest.approx(20.0)


def test_parked_request_owes_only_remaining_decode():
    ctrl = AdmissionController(_StubRuntime())
    ctrl.note_step(0.0)
    ctrl.note_step(1.0)
    req = _req(9, max_new=6)
    ctrl.note_park(ParkedEntry(
        req=req, group=0, blocks=[1, 2], emitted=[5, 6], cache_len=6,
        consumed=4, steps=2, prefill_s=0.0, admit_wall=0.0,
        decode_start_wall=0.0, admitted_s=0.0, parked_s=2.0,
        submit_wall=0.0))
    # 6 - 2 emitted = 4 remaining rounds; no prefill owed (KV is resident)
    assert ctrl.service_estimate(req) == pytest.approx(4.0)
    assert ctrl.parked_group(9) == 0
    assert ctrl.pop_parked(9).blocks == [1, 2]
    assert ctrl.pop_parked(9) is None


def test_pick_victim_guards():
    ctrl = AdmissionController(_StubRuntime())
    inf = float("inf")
    # deadline-less slots always qualify; laziest-then-longest preferred
    assert ctrl.pick_victim(2.0, [(inf, 3.0, "a"), (inf, 7.0, "b")]) == "b"
    # a victim must be strictly lazier than the urgent request
    assert ctrl.pick_victim(5.0, [(4.0, 1.0, "a")]) is None
    # ... and afford the round trip: slack >= urgent + own remaining
    assert ctrl.pick_victim(2.0, [(5.0, 4.0, "a")]) is None
    assert ctrl.pick_victim(2.0, [(6.0, 4.0, "a")]) == "a"


# ---------------------------------------------------------------------------
# composer admission surface
# ---------------------------------------------------------------------------

def test_bs_composer_reorder_and_shed():
    c = BSComposer(_plan(bs=4))
    for rid, dl in ((0, 9.0), (1, 3.0), (2, 6.0)):
        c.add(QueuedItem(payload=_req(rid, deadline=dl), rid=rid))
    c.reorder(lambda it: it.payload.deadline_s)
    assert [it.rid for it in c.queue] == [1, 2, 0]
    assert c.peek().rid == 1
    dropped = c.shed(lambda it: "late" if it.payload.deadline_s < 5 else None)
    assert [(it.rid, v) for it, v in dropped] == [(1, "late")]
    assert [it.rid for it in c.queue] == [2, 0]


def test_mf_composer_orders_across_streams_keeps_frame_order():
    plan = ParallelPlan(service="t", category=FREQ, bs=4, mf=2,
                        admission="sdf")
    c = MFComposer(plan)
    for rid, stream, dl in ((0, 1, 9.0), (1, 1, 9.0), (2, 2, 3.0),
                            (3, 2, 3.0)):
        c.add(QueuedItem(payload=_req(rid, deadline=dl, stream=stream),
                         stream=stream, rid=rid))
    c.reorder(lambda it: it.payload.deadline_s)
    assert c.peek().rid == 2          # urgent stream's head
    batch = c.compose(limit=2)
    # slack-ordered ACROSS streams, FIFO within: stream 2 drains first
    assert [it.rid for it in batch.items] == [2, 3]
    dropped = c.shed(lambda it: "v" if it.stream == 1 else None)
    assert [it.rid for it, _ in dropped] == [0, 1]
    assert 1 not in c.streams         # emptied stream is deleted


# ---------------------------------------------------------------------------
# arena block-table parking
# ---------------------------------------------------------------------------

def test_arena_park_keeps_blocks_and_frees_slot(dense_cfg):
    a = KVArena(dense_cfg, T.init_cache, capacity=2, max_seq_len=32,
                block_size=8)
    assert a.parkable
    s0 = a.alloc(20)                  # 3 blocks
    a.alloc(32)                       # other slot stays live
    blocks = list(a._slot_blocks[s0])
    parked = a.park(s0)
    assert parked == blocks and a.parks == 1
    assert a.parked_blocks == 3
    assert not a.occupancy()[s0]      # slot freed...
    assert (a.block_tables()[s0] == a.trash_block).all()
    assert all(a.block_ref(b) == 1 for b in parked)   # ...KV refs held
    # resume: stitch the parked blocks back, then drop the parked hold
    s1 = a.alloc(20, shared=parked)
    a.release_parked(parked)
    assert a.parked_blocks == 0
    assert list(a._slot_blocks[s1]) == blocks         # same physical KV
    assert all(a.block_ref(b) == 1 for b in parked)   # net refs unchanged
    a.set_len(s1, 13)
    assert int(a.lens[s1]) == 13


def test_arena_park_rejects_stateful_and_free_slots(dense_cfg):
    a = KVArena(dense_cfg, T.init_cache, capacity=2, max_seq_len=32,
                block_size=8)
    with pytest.raises(ValueError):
        a.park(0)                     # not occupied
    # abandoned parked blocks release back to the pool
    s0 = a.alloc(16)
    parked = a.park(s0)
    free0 = a.free_capacity
    a.release_parked(parked)
    assert a.free_capacity == free0 + len(parked)


def test_stateful_arena_not_parkable():
    cfg = toy_config(family="ssm", name="toy-ssm", ssm_state=4,
                     ssm_headdim=16)
    from repro.models.registry import model_api
    api = model_api(cfg)
    a = KVArena(cfg, api.init_cache, capacity=2, max_seq_len=32,
                block_size=8)
    assert a._state_shapes            # ssm keeps per-slot state leaves...
    assert not a.parkable             # ...which cannot survive slot reuse
    s0 = a.alloc(16)
    with pytest.raises(ValueError):
        a.park(s0)


# ---------------------------------------------------------------------------
# engine integration: verdicts, preemption, bit-identity
# ---------------------------------------------------------------------------

def test_expired_deadlines_get_verdicts_not_silent_drops(toy):
    cfg, params = toy
    rt = ServiceRuntime(cfg, params, _plan(bs=2, admission="sdf"))
    results, rejects = [], []
    for i in range(4):
        # deadlines already passed at submission time
        rt.submit(_req(i, deadline=1.0), now=5.0)
    t, _, _ = _drain(rt, 5.0, results, rejects)
    assert not results
    assert sorted(r.req.rid for r in rejects) == [0, 1, 2, 3]
    assert all(r.verdict is Outcome.DEADLINE_MISSED for r in rejects)
    assert rt.admission.verdicts["deadline_missed"] == 4
    # fifo serves the same requests dead — zero behavior change
    rt2 = ServiceRuntime(cfg, params, _plan(bs=2, admission="fifo"))
    results2, rejects2 = [], []
    for i in range(4):
        rt2.submit(_req(i, deadline=1.0), now=5.0)
    _drain(rt2, 5.0, results2, rejects2)
    assert len(results2) == 4 and not rejects2


def _run_policy(cfg, params, policy, preempt=True):
    """The preemption scenario: two lazy long decodes fill both slots,
    then an urgent tight-deadline request arrives.  Logical clock, one
    tick per engine round."""
    rt = ServiceRuntime(cfg, params, _plan(bs=2, admission=policy),
                        preempt=preempt)
    results, rejects, t = [], [], 0.0
    for i in range(2):                # warmup: learn the service EWMA
        rt.submit(_req(100 + i), now=t)
    t, _, _ = _drain(rt, t, results, rejects)
    for i in range(2):                # lazy: no deadline, long decode
        rt.submit(_req(i, max_new=30, prompt=6), now=t)
    for _ in range(2):
        rt.step(now=t)
        t += 1.0
    rt.submit(_req(7, deadline=t + 12.0), now=t)   # urgent but feasible
    t, preempted, resumed = _drain(rt, t, results, rejects)
    return ({r.rid: (list(map(int, r.tokens)), r.finished_s)
             for r in results}, rejects, preempted, resumed, rt)


def test_sdf_preempts_parks_and_resumes_bit_identically(toy):
    cfg, params = toy
    fifo, rej_f, pre_f, res_f, rt_f = _run_policy(cfg, params, "fifo")
    sdf, rej_s, pre_s, res_s, rt_s = _run_policy(cfg, params, "sdf")
    assert (pre_f, res_f, rej_f) == (0, 0, [])
    assert pre_s >= 1 and res_s == pre_s and not rej_s
    assert rt_f.decode_traces == rt_s.decode_traces == 1
    # the urgent request makes its deadline under sdf, misses under fifo
    assert sdf[7][1] <= 12.0 + 4.0 < fifo[7][1]
    # parked-then-resumed greedy decodes are bit-identical to never-parked
    assert set(fifo) == set(sdf)
    for rid in fifo:
        assert fifo[rid][0] == sdf[rid][0], f"rid {rid} tokens diverge"
    # parking flowed through the arena counters and left nothing behind
    arenas = [g.arena for g in rt_s.groups.values()]
    assert sum(a.parks for a in arenas) == pre_s
    assert all(a.parked_blocks == 0 and a.live == 0 for a in arenas)
    assert not rt_s.admission.parked


def test_no_preempt_flag_disables_parking(toy):
    cfg, params = toy
    _, rejects, preempted, _, _ = _run_policy(cfg, params, "sdf",
                                              preempt=False)
    assert preempted == 0
    # without parking the urgent head is still handled with a verdict or
    # served late — either way nothing disappears without one
    assert all(r.verdict in REJECT_VERDICTS for r in rejects)


# ---------------------------------------------------------------------------
# property: random overload interleavings never corrupt another slot
# ---------------------------------------------------------------------------

_EXAMPLES = int(os.environ.get("ADMISSION_EXAMPLES", "5"))

spec = st.tuples(
    st.integers(min_value=2, max_value=8),     # prompt tokens
    st.integers(min_value=1, max_value=8),     # max_new_tokens
    st.integers(min_value=0, max_value=4),     # arrival tick
    st.one_of(st.none(),                       # deadline budget from arrival
              st.floats(min_value=2.0, max_value=60.0)),
)


@settings(max_examples=_EXAMPLES, deadline=None)
@given(specs=st.lists(spec, min_size=3, max_size=10))
def test_random_interleavings_never_corrupt_outputs(specs):
    """Under arbitrary admit/shed/park/resume/evict interleavings on an
    overloaded 2-slot engine, every request that completes produces tokens
    BIT-IDENTICAL to the inert-FIFO oracle, and every submitted request is
    accounted for: served or rejected with exactly one verdict."""
    cfg = toy_config()
    params = T.init(jax.random.PRNGKey(0), cfg)

    def run(policy):
        rt = ServiceRuntime(cfg, params, _plan(bs=2, admission=policy))
        results, rejects, t = [], [], 0.0
        rt.submit(_req(1000), now=t)           # warmup: seed the EWMAs
        t, _, _ = _drain(rt, t, results, rejects)
        tick = 0
        pending = sorted(enumerate(specs), key=lambda x: x[1][2])
        while pending or rt.pending() or rt.in_flight():
            while pending and pending[0][1][2] <= tick:
                rid, (prompt, max_new, _, budget) = pending.pop(0)
                rt.submit(_req(rid, max_new=max_new, prompt=prompt,
                               deadline=0.0 if budget is None
                               else t + budget), now=t)
            st_ = rt.step(now=t)
            results += st_.results
            rejects += st_.rejected
            t += 1.0
            tick += 1
            assert t < 3000.0, "engine failed to drain"
        assert rt.decode_traces == 1
        return rt, results, rejects

    _, oracle, oracle_rej = run("fifo")
    rt, results, rejects = run("sdf")
    assert not oracle_rej
    # accounting: no verdict-less drops (warmup included in results)
    assert len(results) + len(rejects) == len(specs) + 1
    assert len({r.rid for r in results} | {r.req.rid for r in rejects}) \
        == len(specs) + 1
    assert all(r.verdict in REJECT_VERDICTS for r in rejects)
    # bit-identity: whatever completed matches the never-shed oracle
    want = {r.rid: list(map(int, r.tokens)) for r in oracle}
    for r in results:
        assert list(map(int, r.tokens)) == want[r.rid], \
            f"rid {r.rid} corrupted by admission interleaving"
    # nothing left parked; every arena drained clean
    assert not rt.admission.parked
    assert all(g.arena.parked_blocks == 0 and g.arena.live == 0
               for g in rt.groups.values() if g.arena is not None)


# ---------------------------------------------------------------------------
# simulator: fluid-flow sdf model
# ---------------------------------------------------------------------------

def test_simulator_sdf_sheds_doomed_and_counts_verdicts():
    from repro.core.categories import EDGE_P100, ServerSpec
    from repro.simulator.baselines import make_scheduler
    from repro.simulator.engine import SimConfig, Simulation
    from repro.simulator.workload import (WorkloadConfig, generate_requests,
                                          table1_services)
    services = table1_services()
    servers = [ServerSpec(sid=i, num_gpus=1, gpu=EDGE_P100)
               for i in range(2)]
    wl = WorkloadConfig(horizon_s=20.0, load_scale=40.0, seed=3)
    events = generate_requests(services, len(servers), wl)

    def run(policy):
        sched = make_scheduler("EPARA", services, EDGE_P100, seed=1)
        return Simulation(servers, services, sched, events,
                          SimConfig(horizon_s=20.0,
                                    admission_policy=policy)).run()

    fifo, sdf = run("fifo"), run("sdf")
    assert fifo.verdicts == {} and fifo.preemptions == 0
    # sdf sheds requests that cannot make their deadline instead of
    # burning capacity on them: goodput never degrades under overload
    assert sdf.goodput >= fifo.goodput
    assert sdf.verdicts.get("deadline_missed", 0) + \
        sdf.verdicts.get("admit", 0) > 0
    with pytest.raises(ValueError, match="admission_policy"):
        Simulation(servers, services,
                   make_scheduler("EPARA", services, EDGE_P100),
                   events, SimConfig(admission_policy="edf"))
