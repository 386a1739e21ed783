"""Compiled pipeline parallelism — the whole schedule inside ONE jit.

SURVEY.md §7 ranks "pipeline schedule on TPU without a message loop" the
hardest part of this build and prescribes two paths: the host-driven
per-microbatch dispatch (``pipeline.py`` — flexible, matches the reference's
event-loop semantics for arbitrary heterogeneous stages) and a compiled
schedule inside one XLA program (this module — fast, rigid). The reference
has no analog: its TCP message loop *is* the schedule.

The schedule is **GPipe** (fill → steady → drain, all forwards before the
backward which autodiff runs as the reverse drain) — named honestly: it is
*not* 1F1B; activation liveness across the scan is inherently
O(microbatches + stages) tick boundaries per device. What keeps HBM in check
is the **remat policy** (on by default): each stage application is wrapped in
``jax.checkpoint``, so only the tick-boundary activations are saved and all
intra-stage intermediates (conv outputs, BN normalised values, …) are
recomputed during the backward drain — liveness per device drops from
O(ticks × stage_depth) to O(ticks) activations.

Design: SPMD over a ``"stage"`` mesh axis with ``shard_map``. Stage weights
are stacked on a leading axis and sharded so device *i* holds stage *i*'s
slice; activations rotate device-to-device with ``jax.lax.ppermute`` (ICI
neighbor hops — the XLA-native replacement for the reference's
``send to "next_stage"``). The steady-state loop runs
``num_microbatches + num_stages - 1`` ticks; every tick is one fused XLA
step on all devices, so compute on microbatch *i* overlaps the ppermute of
microbatch *i±1* with zero host involvement.

Two engines:

- **Homogeneous** (``make_compiled_pipeline_*``): all stages share one
  params pytree structure and a shape-preserving ``stage_fn`` — the
  zero-overhead path for iso-resolution trunks and transformer stacks.
- **Heterogeneous** (:class:`HeteroCompiledPipeline`): arbitrary
  ``Sequential.split`` partitions — different params structures, activation
  shapes, and BN state per stage. Per-stage pytrees are flattened to padded
  flat vectors stacked over the stage axis; ``lax.switch`` picks this
  device's stage program; activations travel as padded flat buffers.
  Elementwise optimizers (SGD/Adam/…) run directly on the padded flat
  params, so the update step is also a single sharded elementwise op. This
  is what lets the flagship ResNet-18 run through a compiled schedule.

Backward runs by autodiff THROUGH the whole scheduled forward: XLA transposes
the ppermute rotation automatically, yielding the reverse-direction gradient
rotation without any hand-written backward schedule.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.mesh import STAGE_AXIS
from ..nn.layer import Layer
from ..obs import get_tracer
from ..obs.xla import install_compile_listener


def _with_dispatch_span(jitted, name: str, **attrs):
    """Wrap a jitted schedule step in an obs dispatch span.

    The whole schedule is ONE XLA program, so per-stage host spans don't
    exist here (use xprof for intra-dispatch attribution); the span records
    each step's host-side dispatch on the ``pipeline`` track — enough to
    see step cadence and host stalls next to the feed/serve tracks. The
    wrapper forwards ``lower`` (the HLO-inspection tests use it) and is a
    plain passthrough when tracing is disabled."""
    def step(*args):
        # every caller passes the literal "pipe.compiled.step" (mapped in
        # obs/goodput.SPAN_BUCKETS); the indirection is invisible to GP01
        with get_tracer().span(name, track="pipeline", **attrs):  # dcnn: disable=GP01
            return jitted(*args)

    step.lower = jitted.lower
    step.__wrapped__ = jitted
    return step


def stack_stage_params(per_stage_params: list) -> Any:
    """Stack N structurally-identical stage param pytrees along a new leading
    stage axis (device *i* will hold slice *i*)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_stage_params)


def shard_stacked(tree: Any, mesh: Mesh) -> Any:
    """Place stacked stage params with the leading axis sharded over 'stage'."""
    def put(x):
        spec = [STAGE_AXIS] + [None] * (x.ndim - 1)
        return jax.device_put(x, NamedSharding(mesh, P(*spec)))
    return jax.tree_util.tree_map(put, tree)


def make_compiled_pipeline_forward(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    num_stages: int,
    num_microbatches: int,
    mesh: Mesh,
    remat: bool = True,
    data_axis: Optional[str] = None,
):
    """Build ``forward(stacked_params, microbatches) -> outputs`` running the
    GPipe schedule in one jit.

    ``stage_fn(stage_params, x) -> y`` is one stage's computation; activation
    shape must be invariant. ``microbatches``: (num_microbatches, mb, ...) —
    replicated input; outputs: same shape, the last stage's results.
    ``remat=True`` (default) checkpoints each stage application so backward
    recomputes intra-stage intermediates instead of keeping them live across
    the whole schedule.

    ``data_axis``: name of a second mesh axis to data-parallelize over —
    DP×PP composed in the same jit. The microbatch batch dim is sharded over
    it (each data row of the mesh runs the full pipeline on its batch slice;
    ppermutes ride within the row); stage params are replicated across rows,
    so autodiff's shard_map transpose inserts the gradient psum over
    ``data_axis`` automatically. The reference has no analog (its only
    multi-device strategy is the pipeline); this is the pjit-era uplift
    SURVEY.md §7 Stage 5(a) calls for, composed with Stage 5(b).
    """
    if num_microbatches < 1:
        raise ValueError("need at least one microbatch")
    install_compile_listener()  # this builder takes no Sequential
    total_ticks = num_microbatches + num_stages - 1
    if remat:
        stage_fn = jax.checkpoint(stage_fn)

    def per_device(params_slice, mbs):
        # params_slice: this device's stage params (leading axis stripped by
        # shard_map to size 1) — squeeze it.
        params = jax.tree_util.tree_map(lambda x: x[0], params_slice)
        stage = jax.lax.axis_index(STAGE_AXIS)
        mb, rest = mbs.shape[1], mbs.shape[2:]

        fwd_perm = [(i, i + 1) for i in range(num_stages - 1)]

        def tick(carry, t):
            buf, outputs = carry
            # stage 0 injects microbatch t during the fill phase; other
            # stages consume what rotated in last tick.
            inject = jnp.where(t < num_microbatches, t, 0)
            x_in = jnp.where(stage == 0, mbs[inject], buf)
            y = stage_fn(params, x_in)
            # last stage records its result for microbatch (t - S + 1)
            out_idx = t - (num_stages - 1)
            safe_idx = jnp.clip(out_idx, 0, num_microbatches - 1)
            record = jnp.logical_and(stage == num_stages - 1, out_idx >= 0)
            outputs = jax.lax.cond(
                record,
                lambda o: jax.lax.dynamic_update_index_in_dim(o, y, safe_idx, 0),
                lambda o: o,
                outputs)
            # rotate activations one stage forward over ICI (no wrap hop:
            # stage 0 always injects from the microbatch input, so S-1 -> 0
            # would be pure wire waste; non-destinations receive zeros)
            buf = jax.lax.ppermute(y, STAGE_AXIS, fwd_perm)
            return (buf, outputs), None

        buf0 = jnp.zeros((mb, *rest), mbs.dtype)
        outputs0 = jnp.zeros_like(mbs)
        (buf, outputs), _ = jax.lax.scan(
            tick, (buf0, outputs0), jnp.arange(total_ticks))
        # only the last stage holds real outputs; broadcast them to all
        # stages so the result is replicated (psum over one-hot contribution)
        outputs = jax.lax.psum(
            jnp.where(stage == num_stages - 1, outputs, jnp.zeros_like(outputs)),
            STAGE_AXIS)
        return outputs

    mb_spec = P(None, data_axis) if data_axis else P()
    smapped = shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(STAGE_AXIS), mb_spec),
        out_specs=mb_spec,
        check_vma=False,
    )
    jitted = jax.jit(smapped)

    def forward(stacked_params, mbs):
        # The schedule length is baked in at build time; jax dynamic indexing
        # clamps out-of-range microbatch indices, so a mismatched leading dim
        # would silently re-feed/overwrite microbatches instead of erroring.
        if mbs.shape[0] != num_microbatches:
            raise ValueError(
                f"microbatches leading dim {mbs.shape[0]} != "
                f"num_microbatches {num_microbatches} this pipeline was built for")
        return jitted(stacked_params, mbs)

    return forward


def make_compiled_pipeline_train_step(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    loss_fn: Callable[[jax.Array, jax.Array], jax.Array],
    optimizer,
    num_stages: int,
    num_microbatches: int,
    mesh: Mesh,
    remat: bool = True,
    data_axis: Optional[str] = None,
):
    """One jitted train step over the compiled GPipe schedule:
    ``step(stacked_params, opt_state, mb_x, mb_y, lr) ->
    (params, opt_state, loss, outputs)``.

    Gradients come from autodiff through the scheduled forward (XLA
    transposes the ppermute rotation into the backward drain); the optimizer
    update runs sharded — each device updates only its stage's slice. With
    ``data_axis`` set (2-D mesh), the same jit also data-parallelizes over
    that axis: batch sharded, gradient psum inserted by the transpose —
    DP×PP in one dispatch.
    """
    fwd = make_compiled_pipeline_forward(stage_fn, num_stages,
                                         num_microbatches, mesh, remat=remat,
                                         data_axis=data_axis)

    def loss_of(params, mb_x, mb_y):
        outs = fwd(params, mb_x)
        # mean over all microbatches (losses are per-microbatch means)
        losses = jax.vmap(loss_fn)(outs, mb_y)
        return jnp.mean(losses), outs

    def step(params, opt_state, mb_x, mb_y, lr):
        (loss, outs), grads = jax.value_and_grad(loss_of, has_aux=True)(
            params, mb_x, mb_y)
        new_params, new_opt = optimizer.update(grads, opt_state, params, lr)
        return new_params, new_opt, loss, outs

    return _with_dispatch_span(
        jax.jit(step, donate_argnums=(0, 1)), "pipe.compiled.step",
        schedule="gpipe", stages=num_stages,
        microbatches=num_microbatches)


class HeteroCompiledPipeline:
    """Compiled GPipe schedule for **heterogeneous** stages — the engine that
    runs the flagship ResNet-18 (different params structure, activation
    shape, and BN state per stage) inside one jit.

    Mechanism: every stage's params/state pytrees are flattened
    (``ravel_pytree``) into flat fp32 vectors, zero-padded to the widest
    stage, and stacked to ``(S, L)`` arrays sharded over the ``stage`` mesh
    axis. Activations travel between devices as zero-padded flat buffers of
    the widest microbatch activation; ``lax.switch`` dispatches this device's
    stage program, which unpacks its statically-shaped slices. Elementwise
    optimizers run directly on the padded flat params (padding has zero
    gradient, so it stays zero). BN running stats are carried through the
    scan and **gated on microbatch validity**, so pipeline-bubble ticks
    (which compute on garbage buffers) can't pollute statistics; per-stage
    state updates are sequential over microbatches, matching the host-driven
    engine and the reference's per-microbatch BN semantics exactly
    (SURVEY.md §7 hard part 4).

    Numerics parity with :class:`~dcnn_tpu.parallel.pipeline.InProcessPipelineCoordinator`
    (same init, same loss/grad scaling) is pinned by
    ``tests/test_compiled_pipeline.py``.
    """

    def __init__(self, model, num_stages: int, num_microbatches: int,
                 mesh: Mesh, partitioner=None, remat: bool = True,
                 wire_dtype=None):
        from .partitioner import NaivePartitioner

        if model.input_shape is None:
            raise ValueError("model needs a known input_shape")
        self.model = model
        self.num_stages = num_stages
        self.num_microbatches = num_microbatches
        self.mesh = mesh
        self.remat = remat
        # dtype of the inter-stage rotate buffer (the ppermute payload).
        # fp32 default preserves exact parity with the host-driven engine;
        # bf16 halves ICI wire bytes at one rounding step per stage boundary
        # — the same quantization the bf16 mixed-precision mode applies at
        # every op, so training parity holds to bf16 tolerance.
        self.wire_dtype = wire_dtype or jnp.float32
        self.partitions = (partitioner or NaivePartitioner()).get_partitions(
            model, num_stages)
        self.stage_models = model.split(self.partitions)
        self.in_shapes = [tuple(sm.input_shape) for sm in self.stage_models]
        self.out_shapes = [tuple(sm.output_shape()) for sm in self.stage_models]

        # templates (shapes only — eval_shape avoids a real init) →
        # per-stage unravel closures + flat sizes
        tp, tstate = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        tp = jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, a.dtype), tp)
        tstate = jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, a.dtype),
                                        tstate)
        sp = model.split_params(tp, self.partitions)
        ss = model.split_params(tstate, self.partitions)
        self._unravel_p, self._unravel_s = [], []
        self.param_sizes, self.state_sizes = [], []
        for p, s in zip(sp, ss):
            fp, up = ravel_pytree(p)
            fs, us = ravel_pytree(s)
            self._unravel_p.append(up)
            self._unravel_s.append(us)
            self.param_sizes.append(fp.size)
            self.state_sizes.append(fs.size)
        self.Lp = max(self.param_sizes)
        self.Ls = max(max(self.state_sizes), 1)

    def boundary_elems(self, mb: int) -> list:
        """Flat element count of each stage-boundary activation (stage i ->
        i+1) at microbatch size ``mb`` — the EXACT per-hop wire widths the
        rotate path ships. Single source of truth for the engine, the wire
        benchmark, and the HLO-level wire test."""
        return [mb * _prod(self.out_shapes[i])
                for i in range(self.num_stages - 1)]

    def _rotate_exact(self, flat, mb: int, *, backward: bool = False):
        """Ship each stage-boundary activation at its EXACT width
        (VERDICT r3 weak #4 — was: one buffer padded to the widest boundary,
        2.29x useful bytes on ResNet-9/4-stage, plus a wasted S-1 -> 0 wrap
        hop). Boundaries sharing a width share one ppermute (disjoint
        pairs); a device that is no destination receives zeros, so summing
        the zero-padded results reassembles each incoming buffer with no
        masks. ``backward=True`` reverses the pairs — the grad w.r.t. stage
        i+1's input has exactly boundary i's width. Must be called inside
        this pipeline's shard_map (uses the stage collective axis). XLA
        transposes each partial-pair ppermute the same way under autodiff."""
        S = self.num_stages
        L = flat.shape[0]
        bw = self.boundary_elems(mb)
        buf = jnp.zeros_like(flat)
        for w in sorted(set(bw)):
            pairs = [((i + 1, i) if backward else (i, i + 1))
                     for i in range(S - 1) if bw[i] == w]
            recv = jax.lax.ppermute(flat[:w], STAGE_AXIS, pairs)
            buf = buf + jnp.pad(recv, (0, L - w))
        return buf

    def _make_stage_fwd_branch(self, i: int, mb: int, LactTot: int):
        """One stage's forward program on flat-packed operands:
        ``branch(flat_params_vec, flat_state_vec, in_buf, key) ->
        (out_buf, new_flat_state)`` — shared verbatim by the GPipe and 1F1B
        schedules so the unpack/apply/repack contract cannot desync."""
        wire = self.wire_dtype
        in_shapes, out_shapes = self.in_shapes, self.out_shapes

        def branch(fpv, fsv, buf, key):
            p = self._unravel_p[i](fpv[:self.param_sizes[i]])
            s = self._unravel_s[i](fsv[:self.state_sizes[i]])
            # wire dtype -> fp32 at unpack (the stage computes in its own
            # precision policy; bf16 wire only quantizes the hop)
            x = buf[: mb * _prod(in_shapes[i])].reshape(
                mb, *in_shapes[i]).astype(jnp.float32)
            y, s_new = self.stage_models[i].apply(p, s, x, training=True,
                                                  rng=key)
            fs_new, _ = ravel_pytree(s_new)
            out = jnp.pad(y.reshape(-1).astype(wire),
                          (0, LactTot - mb * _prod(out_shapes[i])))
            return out, jnp.pad(fs_new.astype(jnp.float32),
                                (0, self.Ls - fs_new.size))
        return branch

    # -- flat <-> tree helpers --
    def _pack_stacked(self, per_stage_trees, width):
        rows = []
        for tree in per_stage_trees:
            flat, _ = ravel_pytree(tree)
            flat = flat.astype(jnp.float32)
            rows.append(jnp.pad(flat, (0, width - flat.size)))
        return jnp.stack(rows)

    def init(self, key: jax.Array):
        """Init the FULL model once (bit-identical to a single-device run,
        like the host-driven coordinator) and return sharded
        ``(flat_params (S,Lp), flat_state (S,Ls))``."""
        params, state = self.model.init(key)
        sp = self.model.split_params(params, self.partitions)
        ss = self.model.split_params(state, self.partitions)
        fp = self._pack_stacked(sp, self.Lp)
        fs = self._pack_stacked(ss, self.Ls)
        return shard_stacked(fp, self.mesh), shard_stacked(fs, self.mesh)

    def unpack_params(self, flat_params, flat_state):
        """Gather the sharded flat stacks back to per-stage pytrees (for
        checkpointing / eval on one device)."""
        fp = jax.device_get(flat_params)
        fs = jax.device_get(flat_state)
        ps = [self._unravel_p[i](jnp.asarray(fp[i, :self.param_sizes[i]]))
              for i in range(self.num_stages)]
        ss = [self._unravel_s[i](jnp.asarray(fs[i, :self.state_sizes[i]]))
              for i in range(self.num_stages)]
        return ps, ss

    # -- the scheduled step --
    def make_train_step(self, loss_fn, optimizer):
        """Returns jitted ``step(flat_params, opt_state, flat_state, mb_x,
        mb_y, rng, lr) -> (flat_params, opt_state, flat_state, loss,
        logits)``. ``mb_x``: (M, mb, *input_shape); ``mb_y``: (M, mb, ...)."""
        S, M = self.num_stages, self.num_microbatches
        total_ticks = M + S - 1
        in_shapes, out_shapes = self.in_shapes, self.out_shapes
        wire = self.wire_dtype
        # widest per-sample activation crossing any stage boundary (stage-0
        # input or any stage's output) — the flat rotate-buffer width
        max_elems = max([_prod(in_shapes[0])] + [_prod(s) for s in out_shapes])

        rotate_fwd = lambda y_flat, mb: self._rotate_exact(y_flat, mb)

        def scheduled(flat_params1, flat_state1, mbs_flat, rng):
            # shard_map strips the stage axis to size 1 — squeeze
            fp = flat_params1[0]
            fs0 = flat_state1[0]
            stage = jax.lax.axis_index(STAGE_AXIS)
            LactTot = mbs_flat.shape[1]
            mb = LactTot // max_elems

            def make_branch(i):
                branch = self._make_stage_fwd_branch(i, mb, LactTot)
                return jax.checkpoint(branch) if self.remat else branch

            branches = [make_branch(i) for i in range(S)]

            def tick(carry, t):
                buf, fsv, outputs = carry
                inject = jnp.where(t < M, t, 0)
                x_in = jnp.where(stage == 0, mbs_flat[inject], buf)
                mb_idx = jnp.clip(t - stage, 0, M - 1)
                key = jax.random.fold_in(rng, mb_idx)
                y_flat, fs_new = jax.lax.switch(
                    stage, branches, fp, fsv, x_in, key)
                # bubble ticks compute on garbage: gate the state update on
                # this tick carrying a real microbatch through this stage
                valid = jnp.logical_and(t >= stage, t - stage < M)
                fsv = jnp.where(valid, jax.lax.stop_gradient(fs_new), fsv)
                out_idx = t - (S - 1)
                record = jnp.logical_and(stage == S - 1, out_idx >= 0)
                outputs = jax.lax.cond(
                    record,
                    lambda o: jax.lax.dynamic_update_index_in_dim(
                        o, y_flat, jnp.clip(out_idx, 0, M - 1), 0),
                    lambda o: o,
                    outputs)
                buf = rotate_fwd(y_flat, mb)
                return (buf, fsv, outputs), None

            buf0 = jnp.zeros((LactTot,), wire)
            outputs0 = jnp.zeros((M, LactTot), wire)
            (buf, fsv, outputs), _ = jax.lax.scan(
                tick, (buf0, fs0, outputs0), jnp.arange(total_ticks))
            outputs = jax.lax.psum(
                jnp.where(stage == S - 1, outputs, jnp.zeros_like(outputs)),
                STAGE_AXIS)
            return outputs, fsv[None]

        smapped = shard_map(
            scheduled, mesh=self.mesh,
            in_specs=(P(STAGE_AXIS), P(STAGE_AXIS), P(), P()),
            out_specs=(P(), P(STAGE_AXIS)),
            check_vma=False)

        out_elems = _prod(out_shapes[-1])

        def loss_of(flat_params, flat_state, mbs_flat, mb_y, rng):
            outputs, new_state = smapped(flat_params, flat_state, mbs_flat, rng)
            mb = mbs_flat.shape[1] // max_elems
            logits = outputs[:, : mb * out_elems].reshape(
                M, mb, *out_shapes[-1]).astype(jnp.float32)
            losses = jax.vmap(loss_fn)(logits, mb_y)
            return jnp.mean(losses), (logits, new_state)

        def step(flat_params, opt_state, flat_state, mb_x, mb_y, rng, lr):
            mb = mb_x.shape[1]
            # `wire` (captured at build time), NOT self.wire_dtype: a later
            # attribute change must not desync the input cast from the
            # already-compiled scan carry
            mbs_flat = jnp.pad(
                mb_x.reshape(M, -1).astype(wire),
                ((0, 0), (0, mb * max_elems - mb * _prod(in_shapes[0]))))
            (loss, (logits, new_state)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(flat_params, flat_state, mbs_flat,
                                       mb_y, rng)
            new_params, new_opt = optimizer.update(grads, opt_state,
                                                   flat_params, lr)
            return new_params, new_opt, new_state, loss, logits

        return _with_dispatch_span(
            jax.jit(step, donate_argnums=(0, 1, 2)), "pipe.compiled.step",
            schedule="gpipe", stages=S, microbatches=M)


    # ---------------------------------------------------------------- 1F1B
    def make_train_step_1f1b(self, loss_fn, optimizer):
        """One jitted train step over a compiled **1F1B** (PipeDream-flush)
        schedule — same signature and numerics as :meth:`make_train_step`,
        different memory law: the GPipe engine differentiates THROUGH the
        scheduled forward, so autodiff keeps O(M + S) tick-boundary
        activations (+ remat recompute) live per device; here backward is
        hand-scheduled inside the same scan — each device stashes at most
        ``S`` in-flight stage inputs and runs its stage's vjp the moment the
        upstream gradient arrives. This puts the reference's semi-async
        overlap semantics (``coordinator.hpp:273-326`` — backward work
        interleaved with forwards instead of after all of them) inside the
        fast single-dispatch engine.

        Schedule (equal F/B tick costs): stage ``s`` runs ``W_s =
        min(S-s, M)`` warmup forwards at ticks ``s+m``, then alternates
        1F1B — ``F(s,m)`` at ``s+2m``, ``B(s,m)`` at ``2S-s+2m-1`` — over
        ``2(M+S-1)`` total ticks. Forward activations and backward
        gradients rotate in opposite directions through the exact-width
        bucketed ppermutes (:func:`rotate` — same wire law as GPipe). A
        receiver-side latch writes arrivals into the S-slot input stash,
        because the warmup→steady boundary microbatch is produced ``W_s``
        ticks before it is consumed.

        Parity: state updates run in microbatch order at every stage and
        each backward uses the state snapshot its forward saw — the same
        semantics as the host-driven engine and GPipe, so losses/grads/BN
        stats agree to fp tolerance (pinned in tests).
        """
        S, M = self.num_stages, self.num_microbatches
        total_ticks = 2 * (M + S - 1)
        in_shapes, out_shapes = self.in_shapes, self.out_shapes
        psizes, ssizes = self.param_sizes, self.state_sizes
        unravel_p, unravel_s = self._unravel_p, self._unravel_s
        stage_models = self.stage_models
        Lp, Ls = self.Lp, self.Ls
        wire = self.wire_dtype
        max_elems = max([_prod(in_shapes[0])] + [_prod(s) for s in out_shapes])

        rotate = self._rotate_exact

        def scheduled(flat_params1, flat_state1, mbs_flat, mb_y, rng):
            fp = flat_params1[0]
            fs0 = flat_state1[0]
            stage = jax.lax.axis_index(STAGE_AXIS)
            LactTot = mbs_flat.shape[1]
            mb = LactTot // max_elems

            # no checkpoint wrap: 1F1B's backward is hand-scheduled (vjp in
            # the B tick recomputes the stage forward), so nothing is saved
            # across ticks beyond the explicit stashes
            make_fwd_branch = lambda i: self._make_stage_fwd_branch(
                i, mb, LactTot)

            def make_bwd_branch(i):
                last = i == S - 1

                def branch(fpv, fsv_m, x_buf, g_buf, key, y_tgt):
                    s_m = unravel_s[i](fsv_m[:ssizes[i]])
                    xin = x_buf[: mb * _prod(in_shapes[i])].astype(jnp.float32)

                    def f(pslice, xf):
                        p = unravel_p[i](pslice)
                        x = xf.reshape(mb, *in_shapes[i])
                        y, _ = stage_models[i].apply(
                            p, s_m, x, training=True, rng=key)
                        if last:
                            # loss through the wire-dtype quantization, like
                            # the GPipe path (whose loss reads the wire-cast
                            # outputs buffer) — keeps returned loss
                            # consistent with returned logits at any
                            # wire_dtype (review r4 #2)
                            yq = y.astype(wire).astype(jnp.float32)
                            return loss_fn(yq, y_tgt), y
                        # fp32 like the cotangent read off the wire below
                        # (a bf16-precision stage returns bf16)
                        return y.reshape(-1).astype(jnp.float32)

                    if last:
                        loss_m, vjp_fn, _y = jax.vjp(
                            f, fpv[:psizes[i]], xin, has_aux=True)
                        gp, gx = vjp_fn(jnp.float32(1.0))
                    else:
                        loss_m = jnp.float32(0.0)
                        _, vjp_fn = jax.vjp(f, fpv[:psizes[i]], xin)
                        g = g_buf[: mb * _prod(out_shapes[i])].astype(
                            jnp.float32)
                        gp, gx = vjp_fn(g)
                    gp_pad = jnp.pad(gp.astype(jnp.float32),
                                     (0, Lp - gp.size))
                    gx_pad = jnp.pad(gx.astype(wire), (0, LactTot - gx.size))
                    return gp_pad, gx_pad, loss_m

                return branch

            fwd_branches = [make_fwd_branch(i) for i in range(S)]
            bwd_branches = [make_bwd_branch(i) for i in range(S)]

            W = jnp.minimum(S - stage, M)           # warmup forwards
            W_prev = jnp.minimum(S - stage + 1, M)  # sender's warmup count

            def tick(carry, t):
                (fwd_in, bwd_in, stash_x, stash_s, fsv, gacc, outputs,
                 losses) = carry

                d = t - stage
                is_warm_f = jnp.logical_and(d >= 0, d < W)
                is_steady_f = jnp.logical_and(
                    jnp.logical_and(d >= 2 * W, d % 2 == 0), d // 2 < M)
                is_f = jnp.logical_or(is_warm_f, is_steady_f)
                m_f = jnp.clip(jnp.where(is_warm_f, d, d // 2), 0, M - 1)

                num = t - 2 * S + stage + 1
                is_b = jnp.logical_and(
                    jnp.logical_and(num >= 0, num % 2 == 0), num // 2 < M)
                m_b = jnp.clip(num // 2, 0, M - 1)

                # receiver-side latch: if the previous stage ran F(s-1, m_in)
                # last tick, its activation is in fwd_in now — stash it.
                # Sender tick t-1, stage-1: d' = (t-1)-(stage-1) = d.
                snd_warm = jnp.logical_and(d >= 0, d < W_prev)
                snd_steady = jnp.logical_and(
                    jnp.logical_and(d >= 2 * W_prev, d % 2 == 0), d // 2 < M)
                m_in = jnp.clip(jnp.where(snd_warm, d, d // 2), 0, M - 1)
                latch = jnp.logical_and(stage > 0,
                                        jnp.logical_or(snd_warm, snd_steady))
                stash_x = jnp.where(
                    latch,
                    jax.lax.dynamic_update_index_in_dim(
                        stash_x, fwd_in, m_in % S, 0),
                    stash_x)

                phase = jnp.where(is_f, 1, jnp.where(is_b, 2, 0))
                key_f = jax.random.fold_in(rng, m_f)
                key_b = jax.random.fold_in(rng, m_b)
                x_f = jnp.where(
                    stage == 0, mbs_flat[m_f],
                    jax.lax.dynamic_index_in_dim(stash_x, m_f % S, 0,
                                                 keepdims=False))
                zeros_act = jnp.zeros((LactTot,), wire)

                def idle_case(ops):
                    return ops + (zeros_act, zeros_act)

                def f_case(ops):
                    stash_s, fsv, gacc, outputs, losses = ops
                    y, fs_new = jax.lax.switch(stage, fwd_branches,
                                               fp, fsv, x_f, key_f)
                    # snapshot the PRE-forward state for this mb's backward
                    stash_s = jax.lax.dynamic_update_index_in_dim(
                        stash_s, fsv, m_f % S, 0)
                    outputs = jnp.where(
                        stage == S - 1,
                        jax.lax.dynamic_update_index_in_dim(outputs, y, m_f, 0),
                        outputs)
                    return (stash_s, fs_new, gacc, outputs, losses,
                            y, zeros_act)

                def b_case(ops):
                    stash_s, fsv, gacc, outputs, losses = ops
                    x_b = jnp.where(
                        stage == 0, mbs_flat[m_b],
                        jax.lax.dynamic_index_in_dim(stash_x, m_b % S, 0,
                                                     keepdims=False))
                    s_m = jax.lax.dynamic_index_in_dim(stash_s, m_b % S, 0,
                                                       keepdims=False)
                    y_tgt = jax.lax.dynamic_index_in_dim(mb_y, m_b, 0,
                                                         keepdims=False)
                    gp, gx, loss_m = jax.lax.switch(
                        stage, bwd_branches, fp, s_m, x_b, bwd_in, key_b,
                        y_tgt)
                    gacc = gacc + gp
                    losses = jnp.where(
                        stage == S - 1,
                        jax.lax.dynamic_update_index_in_dim(
                            losses, loss_m, m_b, 0),
                        losses)
                    return (stash_s, fsv, gacc, outputs, losses,
                            zeros_act, gx)

                ops = (stash_s, fsv, gacc, outputs, losses)
                (stash_s, fsv, gacc, outputs, losses, send_f, send_b) = \
                    jax.lax.switch(phase, [idle_case, f_case, b_case], ops)

                fwd_in = rotate(send_f, mb, backward=False)
                bwd_in = rotate(send_b, mb, backward=True)
                return (fwd_in, bwd_in, stash_x, stash_s, fsv, gacc,
                        outputs, losses), None

            carry0 = (
                jnp.zeros((LactTot,), wire),            # fwd_in
                jnp.zeros((LactTot,), wire),            # bwd_in
                jnp.zeros((S, LactTot), wire),          # stash_x (S slots!)
                jnp.zeros((S, Ls), jnp.float32),        # stash_s
                fs0,                                    # live state
                jnp.zeros((Lp,), jnp.float32),          # grad accumulator
                jnp.zeros((M, LactTot), wire),          # outputs (last stage)
                jnp.zeros((M,), jnp.float32),           # losses (last stage)
            )
            carry, _ = jax.lax.scan(tick, carry0, jnp.arange(total_ticks))
            _, _, _, _, fsv, gacc, outputs, losses = carry
            last = stage == S - 1
            outputs = jax.lax.psum(
                jnp.where(last, outputs, jnp.zeros_like(outputs)), STAGE_AXIS)
            loss = jax.lax.psum(
                jnp.where(last, jnp.mean(losses), 0.0), STAGE_AXIS)
            return outputs, loss, gacc[None], fsv[None]

        smapped = shard_map(
            scheduled, mesh=self.mesh,
            in_specs=(P(STAGE_AXIS), P(STAGE_AXIS), P(), P(), P()),
            out_specs=(P(), P(), P(STAGE_AXIS), P(STAGE_AXIS)),
            check_vma=False)

        out_elems = _prod(out_shapes[-1])

        def step(flat_params, opt_state, flat_state, mb_x, mb_y, rng, lr):
            mb = mb_x.shape[1]
            mbs_flat = jnp.pad(
                mb_x.reshape(M, -1).astype(wire),
                ((0, 0), (0, mb * max_elems - mb * _prod(in_shapes[0]))))
            outputs, loss, gacc, new_state = smapped(
                flat_params, flat_state, mbs_flat, mb_y, rng)
            logits = outputs[:, : mb * out_elems].reshape(
                M, mb, *out_shapes[-1]).astype(jnp.float32)
            grads = gacc / M   # d(mean loss)/dtheta, matching the GPipe path
            new_params, new_opt = optimizer.update(grads, opt_state,
                                                   flat_params, lr)
            return new_params, new_opt, new_state, loss, logits

        return _with_dispatch_span(
            jax.jit(step, donate_argnums=(0, 1, 2)), "pipe.compiled.step",
            schedule="1f1b", stages=S, microbatches=M)


def _prod(shape) -> int:
    out = 1
    for d in shape:
        out *= int(d)
    return out


class SequentialStageStack:
    """Adapter: build a homogeneous stage stack from ``num_stages`` copies of
    a block ``Layer`` (e.g. a basic residual block), giving the compiled
    schedule a stage_fn + stacked params from the existing layer library."""

    def __init__(self, block: Layer, num_stages: int, input_shape):
        self.block = block
        self.num_stages = num_stages
        self.input_shape = tuple(input_shape)
        self._state_template = None  # empty-leaved structure from init
        if block.output_shape(self.input_shape) != self.input_shape:
            raise ValueError(
                "compiled pipeline requires shape-preserving stages; "
                f"{block.name}: {self.input_shape} -> "
                f"{block.output_shape(self.input_shape)}")

    def init(self, key: jax.Array):
        per_stage = []
        for i in range(self.num_stages):
            p, s = self.block.init(jax.random.fold_in(key, i), self.input_shape)
            if jax.tree_util.tree_leaves(s):
                raise ValueError(
                    "compiled pipeline stages must be stateless (no BN running "
                    "stats); use GroupNorm blocks")
            self._state_template = s
            per_stage.append(p)
        return stack_stage_params(per_stage)

    def stage_fn(self, params, x):
        if self._state_template is None:
            raise RuntimeError("call init() before stage_fn")
        y, _ = self.block.apply(params, self._state_template, x, training=True)
        return y
