"""A run of the harness with the timed path broken underneath: the program
is patched before ``run.main()`` drives the rest of a run as always. Used by
``test_check.py`` under ``JAX_PLATFORMS=cpu`` (the program's own rule, which
is also how the harness's look for a chip is skipped).

    python3 chipbench/tests/broken_run.py <fault> --workload ... --seed ... --seconds ... --trace 0

Faults: ``none``; ``state_unchanged`` (the optimizer returns parameters and
its state as it got them); ``half_batch`` (every second row of a batch left
out, the mean taken over the rest).
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def plant(fault: str) -> None:
    if fault == "none":
        return
    if fault == "state_unchanged":
        from dcnn_tpu.optim import optimizers

        def update(self, grads, opt_state, params, lr=None):
            return params, opt_state
        optimizers.Adam.update = update
    elif fault == "half_batch":
        from dcnn_tpu.train import trainer

        real = trainer.make_train_step

        def make_train_step(model, loss_fn, optimizer, *a, jit=True, **kw):
            import jax
            kw.pop("donate", None)
            base = real(model, loss_fn, optimizer, *a, jit=False, **kw)

            def step(ts, x, y, rng, lr):
                ts, loss, logits = base(ts, x[::2], y[::2], rng, lr)
                return ts, loss, jax.numpy.repeat(logits, 2, axis=0)
            return jax.jit(step, donate_argnums=(0,)) if jit else step
        trainer.make_train_step = make_train_step

    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    fault = sys.argv.pop(1)
    import run
    # the program reads its precision from the environment when it is imported
    import json
    args = dict(zip(sys.argv[1::2], sys.argv[2::2]))
    spec = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    cell = [w for w in spec["workloads"] if w["name"] == args["--workload"]][0]
    cfg = json.load(open(os.path.join(run.HERE, "configs", cell["config"] + ".json")))
    os.environ["DCNN_PRECISION"] = cfg["precision"]
    plant(fault)
    sys.exit(run.main())
