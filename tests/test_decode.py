"""Continuous-batching decode tests (dcnn_tpu/serve/decode.py + kvcache.py
+ models/decoder.py + the nn attention decode path).

Contracts (ISSUE 20 acceptance):

- ORACLE: the single-token decode path (paged engine AND dense
  ``decode_dense``) reproduces the full-sequence causal forward's greedy
  choices exactly — same mask convention, same precision;
- BIT-IDENTITY: a sequence's continuously-batched greedy output is
  bit-identical to the same sequence decoded alone
  (``decode_reference``), asserted across MULTIPLE admission
  interleavings (everything-up-front vs staggered mid-flight admission)
  and under forced preemption;
- ZERO RECOMPILES: admitting into a running batch triggers no compile
  once the (batch-bucket, page-bucket) set is warmed — asserted via the
  compile listener's ``compile_total`` delta;
- NO ORPHANS: an injected crash at ``decode.step`` fails every accepted
  sequence (active AND queued) typed; an ``InjectedFault`` at
  ``decode.admit`` fails exactly that sequence and the rest complete;
- the page pool allocates all-or-nothing, recycles through its free
  list, and never hands out the null page.

Engine construction compiles a bucket lattice (~seconds on CPU), so the
module builds TWO engines total (module-scoped fixtures): the main one
and a page-starved one for eviction.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dcnn_tpu.models import MHADecoder
from dcnn_tpu.obs.registry import MetricsRegistry
from dcnn_tpu.resilience import FaultPlan
from dcnn_tpu.resilience.faults import InjectedCrash, InjectedFault
from dcnn_tpu.serve import (
    ContinuousBatcher, DecodeEngine, DrainingError, KVPagePool,
    OutOfPagesError, QueueFullError, decode_reference, suggest_num_pages,
)
from dcnn_tpu.serve.metrics import DecodeMetrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROMPTS = [[1, 5, 2], [3, 3], [7, 1, 2, 4], [2], [9, 8, 7, 1, 2], [4, 6]]


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


@pytest.fixture(scope="module")
def model():
    return MHADecoder(vocab_size=13, embed_dim=16, num_heads=2,
                      num_layers=2, max_seq_len=32)


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def engine(model, params):
    """Main engine: 4 slots x 4 pages of 4 — plus a private registry so
    compile accounting is observable without the process-global one."""
    reg = MetricsRegistry()
    eng = DecodeEngine(model, params, max_slots=4, page_size=4,
                       max_pages_per_seq=4, registry=reg)
    return eng


@pytest.fixture(scope="module")
def starved_engine(model, params):
    """Page-starved twin: 4 slots that cannot all hold max-length
    sequences (7 usable pages for up to 16 demanded) — forces the
    preempt-and-recompute path."""
    return DecodeEngine(model, params, max_slots=4, page_size=4,
                        max_pages_per_seq=4, num_pages=8, warmup=False,
                        registry=MetricsRegistry())


def greedy_oracle(model, params, prompt, max_new):
    """Greedy decode via the full-sequence causal forward — the slow
    reference everything else must reproduce exactly."""
    toks = list(prompt)
    for _ in range(max_new):
        logits = model.apply(params, jnp.asarray([toks], jnp.int32))
        toks.append(int(jnp.argmax(logits[0, -1])))
    return np.asarray(toks[len(prompt):], np.int32)


# ------------------------------------------------------------ oracle

def test_reference_matches_full_forward_oracle(model, params, engine):
    for prompt in PROMPTS[:3]:
        want = greedy_oracle(model, params, prompt, 6)
        got = decode_reference(engine, prompt, max_new_tokens=6)
        assert np.array_equal(got, want), (prompt, got, want)


def test_decode_dense_matches_oracle(model, params):
    """The un-paged dense-cache decode path (models/decoder.decode_dense
    over nn decode_qkv/decode/decode_attend) replays a sequence to the
    same greedy choices as the full forward."""
    prompt = [1, 5, 2, 9]
    b, t, e = 1, 16, model.embed_dim
    k = [jnp.zeros((b, t, e)) for _ in range(model.num_layers)]
    v = [jnp.zeros((b, t, e)) for _ in range(model.num_layers)]
    toks = list(prompt)
    generated = []
    for pos in range(len(prompt) + 5 - 1):
        x_t = model.embed_tokens(params, jnp.asarray([toks[pos]], jnp.int32))
        logits, k, v = model.decode_dense(
            params, x_t, k, v, jnp.asarray([pos], jnp.int32))
        if pos == len(toks) - 1:
            nxt = int(jnp.argmax(logits[0]))
            toks.append(nxt)
            generated.append(nxt)
    want = greedy_oracle(model, params, prompt, 5)
    assert np.array_equal(np.asarray(generated, np.int32), want)


def test_inactive_rows_fully_masked(model, params, engine):
    """A position of -1 marks an inactive row: its attention output is
    exactly zero (the NEG_INF mask underflows to 0.0), so padding rows
    cannot perturb anything."""
    blk, bp = model.blocks[0], params["blocks"][0]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, model.embed_dim))
    q, _, _ = blk.decode_qkv(bp, x)
    ctx = jax.random.normal(jax.random.PRNGKey(2), (2, 8, model.embed_dim))
    out = blk.decode_attend(bp, q, ctx, ctx,
                            jnp.asarray([-1, -1], jnp.int32))
    # fully-masked rows: softmax zeroed, so only the output projection
    # bias survives — identical for any context content
    out2 = blk.decode_attend(bp, q, ctx * 100.0, ctx * -3.0,
                             jnp.asarray([-1, -1], jnp.int32))
    assert np.array_equal(np.asarray(out), np.asarray(out2))


# ------------------------------------------------- bit-identity

def _run_continuous(engine, submit_plan, max_new=5, **kw):
    """Drive a sync-mode batcher through `submit_plan`: a list of
    (step_at, prompt) pairs — each prompt submitted after `step_at`
    scheduler steps have run. Returns {prompt_index: result}."""
    cb = ContinuousBatcher(engine, start=False, clock=FakeClock(), **kw)
    futs = {}
    plan = sorted(range(len(submit_plan)), key=lambda i: submit_plan[i][0])
    steps = 0
    while plan or cb.active_slots or cb.queue_depth:
        while plan and submit_plan[plan[0]][0] <= steps:
            i = plan.pop(0)
            futs[i] = cb.submit(submit_plan[i][1], max_new_tokens=max_new)
        if cb.step() == 0 and not plan:
            break
        steps += 1
    return {i: f.result(timeout=5) for i, f in futs.items()}


def test_continuous_bit_identical_upfront(engine):
    """Interleaving 1: everything submitted before the first step."""
    plan = [(0, p) for p in PROMPTS]
    got = _run_continuous(engine, plan)
    for i, p in enumerate(PROMPTS):
        want = decode_reference(engine, p, max_new_tokens=5)
        assert np.array_equal(got[i], want), (i, got[i], want)


def test_continuous_bit_identical_staggered(engine):
    """Interleaving 2: sequences admitted MID-FLIGHT into a running
    batch at different step boundaries — the continuous-batching case.
    Output must still be bit-identical per sequence."""
    plan = [(0, PROMPTS[0]), (0, PROMPTS[1]), (2, PROMPTS[2]),
            (3, PROMPTS[3]), (5, PROMPTS[4]), (7, PROMPTS[5])]
    got = _run_continuous(engine, plan)
    for i, (_, p) in enumerate(plan):
        want = decode_reference(engine, p, max_new_tokens=5)
        assert np.array_equal(got[i], want), (i, got[i], want)


def test_preemption_recompute_bit_identical(starved_engine):
    """Under page starvation the scheduler preempts the newest sequence
    and replays it after readmission — still bit-identical, and the
    eviction counter proves the path actually ran."""
    metrics = DecodeMetrics(clock=FakeClock())
    prompts = [[1, 5, 2, 4, 6], [3, 3, 1, 1], [7, 1, 2, 4, 5, 6],
               [2, 9, 8, 4], [9, 8, 7, 1, 2]]
    plan = [(0, p) for p in prompts]
    got = _run_continuous(starved_engine, plan, max_new=8, metrics=metrics)
    for i, p in enumerate(prompts):
        want = decode_reference(starved_engine, p, max_new_tokens=8)
        assert np.array_equal(got[i], want), (i, got[i], want)
    s = metrics.snapshot()
    assert s["evictions"] > 0, "starved pool must have preempted"
    assert s["completions"] == len(prompts)


def test_eos_stops_decode(model, params, engine):
    """eos_id terminates a sequence early, EOS token included."""
    ref = decode_reference(engine, [1, 5, 2], max_new_tokens=8)
    eos = int(ref[0])  # first generated token as EOS -> length-1 output
    got = _run_continuous(engine, [(0, [1, 5, 2])], max_new=8)[0]
    cb_ref = decode_reference(engine, [1, 5, 2], max_new_tokens=8,
                              eos_id=eos)
    assert np.array_equal(cb_ref, ref[:1])
    cb = ContinuousBatcher(engine, start=False, clock=FakeClock())
    fut = cb.submit([1, 5, 2], max_new_tokens=8, eos_id=eos)
    while cb.step():
        pass
    assert np.array_equal(fut.result(timeout=5), ref[:1])
    assert np.array_equal(got, ref)


# ------------------------------------------------- zero recompiles

def test_admission_never_recompiles(engine):
    """Acceptance: once the (batch, page) bucket lattice is warm,
    admitting sequences into a running batch causes ZERO new compiles —
    the compile listener's compile_total is flat across a staggered run
    that exercises batch sizes 1..4 and growing page tables."""
    from dcnn_tpu.obs import get_registry
    from dcnn_tpu.obs.xla import install_compile_listener

    # the compile listener (JAX's own events, on the shared registry) sees
    # every backend compile of the process, a plain jit's as much as a
    # session's
    install_compile_listener()
    count = get_registry().counter("compile_total")
    before = count.value
    plan = [(0, PROMPTS[0]), (1, PROMPTS[1]), (2, PROMPTS[2]),
            (3, PROMPTS[3]), (4, PROMPTS[4]), (6, PROMPTS[5])]
    got = _run_continuous(engine, plan, max_new=7)
    assert len(got) == len(plan)
    after = count.value
    assert after == before, (
        f"admission recompiled: compile_total {before} -> {after}")


# ------------------------------------------------- fault injection

def test_injected_crash_mid_step_fails_all_typed(engine):
    """resilience/faults.py trip point "decode.step": a crash mid-decode
    fails EVERY accepted sequence — active and still-queued — with the
    injected exception. Nothing is silently dropped, mirroring the
    DynamicBatcher accepted-ledger contract."""
    cb = ContinuousBatcher(engine, start=False, clock=FakeClock(),
                           max_slots=2)
    futs = [cb.submit(p, max_new_tokens=5) for p in PROMPTS[:4]]
    assert cb.step() > 0  # step 0 runs clean
    with FaultPlan().arm("decode.step", exc=InjectedCrash):
        with pytest.raises(InjectedCrash):
            cb.step()
    for fut in futs:  # active (2) AND queued (2): all resolved, typed
        assert fut.done()
        with pytest.raises(InjectedCrash):
            fut.result(timeout=0)
    assert cb.engine.pool.pages_in_use == 0  # pages all recycled
    assert cb.health_reason() is not None
    with pytest.raises(DrainingError):
        cb.submit([1, 2], max_new_tokens=2)


def test_injected_fault_at_admit_fails_one_sequence(engine):
    """Trip point "decode.admit" with a plain InjectedFault: exactly the
    tripped sequence's future fails (typed), every other sequence decodes
    to the bit-identical reference."""
    cb = ContinuousBatcher(engine, start=False, clock=FakeClock())
    with FaultPlan().arm("decode.admit", at=1, times=1):  # 2nd admission
        futs = [cb.submit(p, max_new_tokens=4) for p in PROMPTS[:3]]
        while cb.step():
            pass
    with pytest.raises(InjectedFault):
        futs[1].result(timeout=5)
    for i in (0, 2):
        want = decode_reference(engine, PROMPTS[i], max_new_tokens=4)
        assert np.array_equal(futs[i].result(timeout=5), want)


# ------------------------------------------------- intake contract

def test_submit_validation(engine):
    cb = ContinuousBatcher(engine, start=False, clock=FakeClock())
    with pytest.raises(ValueError):
        cb.submit([], max_new_tokens=2)
    with pytest.raises(ValueError):
        cb.submit([1, 2], max_new_tokens=0)
    with pytest.raises(ValueError):
        cb.submit([99], max_new_tokens=2)  # token outside vocab
    with pytest.raises(ValueError):  # prompt + max_new > max context
        cb.submit([1] * 10, max_new_tokens=engine.max_context)


def test_queue_full_sheds_typed(engine):
    metrics = DecodeMetrics(clock=FakeClock())
    cb = ContinuousBatcher(engine, start=False, clock=FakeClock(),
                           queue_capacity=2, metrics=metrics)
    cb.submit([1], max_new_tokens=2)
    cb.submit([2], max_new_tokens=2)
    with pytest.raises(QueueFullError):
        cb.submit([3], max_new_tokens=2)
    assert metrics.snapshot()["sequences_shed"] == 1
    while cb.step():
        pass


def test_shutdown_without_drain_fails_pending(engine):
    from dcnn_tpu.serve import ShutdownError
    cb = ContinuousBatcher(engine, start=False, clock=FakeClock())
    futs = [cb.submit(p, max_new_tokens=4) for p in PROMPTS[:3]]
    cb.shutdown(drain=False)
    for fut in futs:
        with pytest.raises(ShutdownError):
            fut.result(timeout=0)
    with pytest.raises(DrainingError):
        cb.submit([1], max_new_tokens=2)
    assert engine.pool.pages_in_use == 0


def test_threaded_drain_completes_everything(engine):
    """The threaded mode (the only sleep-ful decode test): submit, drain,
    every future resolves to the reference."""
    cb = ContinuousBatcher(engine, queue_capacity=8)
    futs = [cb.submit(p, max_new_tokens=4) for p in PROMPTS[:4]]
    cb.drain(timeout=60)
    for p, fut in zip(PROMPTS, futs):
        want = decode_reference(engine, p, max_new_tokens=4)
        assert np.array_equal(fut.result(timeout=5), want)
    assert cb.health_reason() is not None  # drained = not accepting


# ------------------------------------------------- page pool

def test_page_pool_geometry_and_allocation():
    pool = KVPagePool(num_layers=2, embed_dim=8, page_size=4, num_pages=6)
    assert pool.pages_for(0) == 0
    assert pool.pages_for(1) == 1
    assert pool.pages_for(4) == 1
    assert pool.pages_for(5) == 2
    assert pool.page_bytes == 2 * 2 * 4 * 8 * 4
    assert pool.ensure("a", 3) == 1
    assert pool.ensure("a", 3) == 1  # idempotent
    assert pool.ensure("a", 9) == 3
    assert pool.pages_in_use == 3 and pool.pages_free == 2
    t = pool.table("a", 4)
    assert t.dtype == np.int32 and t.shape == (4,)
    assert 0 not in t[:3]  # the null page is never allocated
    assert t[3] == 0  # padding IS the null page
    with pytest.raises(ValueError):
        pool.table("a", 2)  # table wider than the bucket = caller bug


def test_page_pool_all_or_nothing_and_recycle():
    pool = KVPagePool(num_layers=1, embed_dim=4, page_size=2, num_pages=4)
    pool.ensure("a", 4)  # 2 of 3 usable pages
    with pytest.raises(OutOfPagesError):
        pool.ensure("b", 4)  # needs 2, only 1 free
    assert pool.num_seq_pages("b") == 0  # nothing leaked
    assert pool.pages_free == 1
    assert pool.release("a") == 2
    assert pool.release("a") == 0  # unknown/already-released: no-op
    assert pool.ensure("b", 4) == 2  # recycled pages satisfy it now
    snap = pool.snapshot()
    assert snap["pages_in_use"] == 2 and snap["sequences"] == 1


def test_suggest_num_pages_defaults_on_cpu():
    # CPU backends report no memory stats -> the explicit default
    assert suggest_num_pages(1024, default=37) == 37
    with pytest.raises(ValueError):
        suggest_num_pages(0)
    with pytest.raises(ValueError):
        suggest_num_pages(1024, fraction=0.0)


# ------------------------------------------------- metrics

def test_decode_metrics_none_until_data():
    m = DecodeMetrics(clock=FakeClock())
    s = m.snapshot()
    assert s["ttft_p50_ms"] is None and s["slot_occupancy"] is None
    assert s["tokens"] == 0 and s["completions"] == 0


def test_decode_metrics_exact_under_fake_clock():
    clk = FakeClock()
    m = DecodeMetrics(clock=clk)
    m.record_submit()
    m.record_admit()
    clk.advance(0.25)
    m.record_ttft(0.25)
    for _ in range(4):
        m.record_token()
    m.record_step(2, 4)
    m.record_step(4, 4)
    m.record_pages(6)
    clk.advance(0.75)
    s = m.snapshot()
    assert s["ttft_p50_ms"] == 250.0 and s["ttft_p99_ms"] == 250.0
    assert s["slot_occupancy"] == 0.75
    assert s["tokens_per_sec"] == 4.0  # 4 tokens over 1.0s
    assert s["pages_in_use"] == 6


def test_decode_metrics_prometheus_surface():
    clk = FakeClock()
    m = DecodeMetrics(clock=clk)
    m.record_submit()
    m.record_token()
    m.record_ttft(0.1)
    m.record_step(1, 2)
    clk.advance(1.0)
    text = m.prometheus()
    for name in ("decode_tokens_total", "decode_sequences_submitted_total",
                 "decode_steps_total", "decode_active_slots",
                 "decode_pages_in_use", "decode_queue_depth",
                 "decode_ttft_seconds", "decode_admissions_total",
                 "decode_evictions_total", "decode_completions_total",
                 "decode_prefill_tokens_total", "decode_sequences_shed_total",
                 "decode_ttft_window_p50_ms", "decode_ttft_window_p99_ms",
                 "decode_slot_occupancy", "decode_tokens_per_sec"):
        assert f"\n{name}" in text or text.startswith(name), name
    assert text.endswith("\n")


# ------------------------------------------------- engine surface

def test_engine_bucket_math(engine):
    assert engine.bucket_sizes == [1, 2, 4]
    assert engine.page_buckets == [1, 2, 4]
    assert engine.bucket_for(3) == 4
    assert engine.page_bucket_for(0) == 1
    assert engine.page_bucket_for(3) == 4
    with pytest.raises(ValueError):
        engine.bucket_for(5)
    with pytest.raises(ValueError):
        engine.page_bucket_for(5)
    with pytest.raises(ValueError):  # unbucketed shape: typed, no retrace
        engine.run_step(np.zeros(3, np.int32), np.zeros(3, np.int32),
                        np.zeros((3, 1), np.int32), engine.pool.k,
                        engine.pool.v)


def test_engine_rejects_context_beyond_model(model, params):
    with pytest.raises(ValueError):
        DecodeEngine(model, params, max_slots=1, page_size=32,
                     max_pages_per_seq=2)  # 64 > 32


def test_engine_compile_stats_cover_lattice(engine):
    assert set(engine.compile_stats) == {
        (b, mp) for b in engine.bucket_sizes for mp in engine.page_buckets}
    for st in engine.compile_stats.values():
        assert st["compile_s"] >= 0


# ------------------------------------------------- example smoke

def test_serve_decode_example_imports():
    """Import smoke for examples/serve_decode.py (same isolation dance as
    the other example smokes: the examples dir must resolve `common`)."""
    import importlib

    ex_dir = os.path.join(REPO, "examples")
    saved_common = sys.modules.pop("common", None)
    sys.path.insert(0, ex_dir)
    try:
        mod = importlib.import_module("serve_decode")
        assert callable(mod.main)
    finally:
        sys.path.remove(ex_dir)
        sys.modules.pop("serve_decode", None)
        sys.modules.pop("common", None)
        if saved_common is not None:
            sys.modules["common"] = saved_common
