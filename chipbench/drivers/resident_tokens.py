"""Driver ``resident_tokens``: a language model trained from a token split
staged once to HBM (``TokenDataset``), each epoch one dispatch
(``Trainer.train_epoch`` -> ``_train_epoch_resident``), built as
``examples/lm_trainer.py`` builds it. The window is whole epochs and counts
**sequences** (``train_img_per_s`` of such a cell is sequences a second).

The output check follows the first epoch, which is also the warm-up: the
epoch's mean loss, AdamW's first moment and the parameters' change after it.
The reference repeats that epoch from the same weights on the same batches,
which it derives from the seed by the feed's stated recipe:

    tokens = lm_trainer.zipf_tokens(seed, n, S + 1, vocab, exponent)
    kperm, kstep = split(epoch_key);  perm = permutation(fold_in(kperm, 0), n)
    batch i = rows perm[i*B:(i+1)*B];  input row[:-1], labels row[1:]
    learning rate: the configuration's, constant

where ``epoch_key = fold_in(fold_in(PRNGKey(seed), epoch), epoch)``.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from trainer_common import TrainerJob  # noqa: E402


class Job(TrainerJob):
    """``TrainerJob``'s state, epoch key, failed steps and close; its own
    construction (a language model is not built from a layer list)."""

    def build(self):
        import jax
        import jax.numpy as jnp
        import lm_trainer
        from common import setup
        from dcnn_tpu.data import TokenDataset
        from dcnn_tpu.models import create_model
        from dcnn_tpu.train.trainer import TrainState

        import refrun

        b, cfg = self.bench, self.bench.cfg
        opt = cfg["optimizer"]
        os.environ["LEARNING_RATE"] = str(opt["learning_rate"])
        os.environ["ADAM_BETA2"] = str(opt["beta2"])
        os.environ["WEIGHT_DECAY"] = str(opt["weight_decay"])
        self.tcfg = setup("chipbench " + b.cell["name"])
        model = create_model(cfg["program_model"])
        other = {k: cfg[k] for k in model.config if k in cfg and cfg[k] != model.config[k]}
        if other:
            if not b.rehearsal:
                raise RuntimeError(f"the configuration file and the program's "
                                   f"{cfg['program_model']!r} differ in {sorted(other)}")
            model = model.resized(**other)
        ds = cfg["dataset"]
        self.tokens = lm_trainer.zipf_tokens(
            b.seed, ds["train_sequences"], cfg["seq_len"] + 1, cfg["vocab_size"],
            ds["zipf_exponent"])
        self.loader = TokenDataset(self.tokens, cfg["vocab_size"],
                                   batch_size=self.tcfg.batch_size)
        self.trainer = lm_trainer.build(self.tcfg, model)
        params, _ = refrun.seeded_weights(cfg, b.seed)
        want_p, want_s = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)  # noqa: E731
        if shapes(want_p) != shapes(params):
            raise RuntimeError("the reference's parameter tree is not the program's")
        self.params0 = jax.device_get(params)
        zeros = jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, a.dtype), want_s)
        self.state = TrainState(params, zeros, self.trainer.optimizer.init(params),
                                jnp.zeros((), jnp.int32))
        self.rng = jax.random.PRNGKey(self.tcfg.seed)
        self.epoch = 1
        self.steps_per_epoch = int(self.loader.steps_per_epoch)
        self.batch = int(self.loader.batch_size)

    def one_epoch(self):
        """An epoch through the trainer; the held pairs it computed go to the
        harness's counters, for ``expert_gmm_roofline``."""
        from dcnn_tpu.obs import get_registry

        before = get_registry().snapshot().get("moe_pairs_held_total", 0)
        self.state, loss, _ = self.trainer.train_epoch(
            self.state, self.loader, self.epoch_key(), self.epoch)
        self.epoch += 1
        held = get_registry().snapshot().get("moe_pairs_held_total", 0) - before
        return float(loss), held

    def warm(self):
        import jax

        self.first_key = np.asarray(jax.random.fold_in(self.epoch_key(), self.epoch))
        loss, _ = self.one_epoch()
        leaves = jax.tree_util.tree_leaves
        p1 = jax.device_get(leaves(self.state.params))
        m1 = jax.device_get(leaves(self.state.opt_state["m"]))
        p0 = leaves(self.params0)
        self.program = {"losses": [loss], "moment": m1,
                        "change": [a - b for a, b in zip(p1, p0)]}

    def run_window(self, window):
        b = self.bench
        held = b.counters.setdefault("moe_pairs_held_by_epoch", [])
        window.open()
        going = window.boundary()
        while going:
            with b.span("epoch"):
                loss, pairs = self.one_epoch()
            self.losses.append(loss)
            held.append(pairs)
            going = window.boundary(self.steps_per_epoch * self.batch)
            window.epoch_turn()

    def program_readings(self):
        return self.program

    def reference_readings(self, quantize_name=None, rows=None):
        """The first epoch by the plain reference, a jitted step at a time
        (its state donated, so that 16 bytes a parameter is all it holds)."""
        import jax
        import jax.numpy as jnp

        import refrun

        cfg = self.bench.cfg
        ref = refrun.load_reference(cfg)
        q = ref.quantizer(quantize_name)
        n, bsz, k = len(self.tokens), self.batch, self.steps_per_epoch
        kperm, _ = jax.random.split(jnp.asarray(self.first_key))
        perm = jax.random.permutation(jax.random.fold_in(kperm, 0), n)
        idx = np.asarray(perm[:k * bsz]).reshape(k, bsz)

        def step(p, o, x, y, lr):
            p, _, o, loss, _ = ref.train_step(cfg, p, {}, o, x, y, lr, q, rows)
            return p, o, loss
        step = jax.jit(step, donate_argnums=(0, 1))
        lr = np.float32(cfg["optimizer"]["learning_rate"])

        t0 = time.perf_counter()
        with jax.default_matmul_precision("highest"):
            params = jax.device_put(self.params0)
            opt = ref.adam_init(params)
            losses, marks = [], []
            for i in range(k):
                batch = jnp.asarray(self.tokens[idx[i]])
                params, opt, loss = step(params, opt, batch[:, :-1], batch[:, 1:], lr)
                losses.append(float(loss))
                marks.append(time.perf_counter() - t0)
            leaves = jax.tree_util.tree_leaves
            p1, m1 = jax.device_get(leaves(params)), jax.device_get(leaves(opt["m"]))
        del params, opt
        print(f"chipbench reference: first step (with its compile) {marks[0]:.1f} s, "
              f"{k} steps {marks[-1]:.1f} s, state back on the host "
              f"{time.perf_counter() - t0:.1f} s")
        return {"losses": [float(np.mean(losses))], "moment": m1,
                "change": [a - b for a, b in zip(p1, leaves(self.params0))]}
