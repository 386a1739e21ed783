"""Pipeline-parallel trainer (reference ``examples/sync_pipeline_coordinator
.cpp`` + ``semi_async_pipeline_coordinator.cpp`` + ``network_worker.cpp``,
collapsed into the in-process deployment — stages on separate TPU chips of
one slice instead of TCP worker processes).

Env: NUM_STAGES (default 2), SCHEDULE=sync|semi_async, NUM_MICROBATCHES,
MODEL (zoo name, default resnet9_cifar10), plus TrainingConfig vars.
"""

import jax
from common import setup

from dcnn_tpu.models import create_model
from dcnn_tpu.optim import Adam
from dcnn_tpu.parallel import FlopBalancedPartitioner, InProcessPipelineCoordinator
from dcnn_tpu.parallel.pipeline import train_pipeline_epoch
from dcnn_tpu.data import SyntheticClassificationLoader
from dcnn_tpu.utils.env import get_env


def build(cfg, model_name, num_stages, num_samples=1024):
    """Deployed coordinator + synthetic loader — ``main`` below and
    ``chip_smoke.py`` both go through here."""
    model = create_model(model_name)
    shape = model.input_shape
    num_classes = model.output_shape()[0]

    train_loader = SyntheticClassificationLoader(
        num_samples, shape, num_classes, batch_size=cfg.batch_size,
        seed=cfg.seed)

    devs = jax.devices()
    devices = [devs[i % len(devs)] for i in range(num_stages)]
    coord = InProcessPipelineCoordinator(
        model, Adam(cfg.learning_rate), "softmax_crossentropy",
        num_stages=num_stages, partitioner=FlopBalancedPartitioner(),
        devices=devices, num_microbatches=cfg.num_microbatches or 4,
        track_load=True)
    coord.deploy_stages(jax.random.PRNGKey(cfg.seed))
    print(f"partitions: {coord.partitions} over devices "
          f"{[str(d) for d in devices]}")
    return coord, train_loader


def main():
    cfg = setup("pipeline_trainer")
    schedule = get_env("SCHEDULE", "semi_async")
    coord, train_loader = build(cfg, get_env("MODEL", "resnet9_cifar10"),
                                get_env("NUM_STAGES", 2))
    print(f"schedule={schedule}")

    for epoch in range(1, cfg.epochs + 1):
        train_loader.shuffle(epoch)
        loss, acc = train_pipeline_epoch(coord, train_loader, cfg.learning_rate,
                                         jax.random.PRNGKey(epoch), schedule)
        print(f"epoch {epoch}: loss {loss:.4f} acc {acc:.4f}")
        for sid, rep in enumerate(coord.collect_load_reports()):
            print(f"  stage {sid}: fwd {rep['avg_forward_ms']:.2f}ms "
                  f"bwd {rep['avg_backward_ms']:.2f}ms")
        if get_env("PIPELINE_PROFILE", 0):
            # per-layer table from every stage (reference PRINT_PROFILING)
            from dcnn_tpu.parallel.pipeline import format_profiling
            print(format_profiling(coord.collect_profiling()))
            coord.clear_profiling()


if __name__ == "__main__":
    main()
