"""Language-model trainer: a config-built decoder (``MODEL``, default the
zoo's ``deepseek_v2_lite_ep8``: one expert-parallel rank's share of
DeepSeek-V2-Lite; ``MODEL=kimi_linear_48b_ep32``: one of 32 ranks' share of
Kimi-Linear-48B-A3B, Kimi Delta Attention beside latent attention, the same
decoder class) trained on next-token prediction from a token split that sits
in HBM (``TokenDataset``), each epoch one dispatch.

Environment, beside ``common.setup``'s (``BATCH_SIZE`` counts sequences,
``EPOCHS``, ``LEARNING_RATE`` constant, ``SEED``): ``MODEL``; ``SEQ_LEN``
(4096) and ``TRAIN_SEQUENCES`` (32) of ids drawn from the seed by Zipf's
law; ``ADAM_BETA2`` (0.95), ``WEIGHT_DECAY`` (0.1). ``DCNN_PRECISION=bf16``
is the mode the model is meant for. There is no validation on tokens yet.
"""

import numpy as np
from common import setup

from dcnn_tpu.data import TokenDataset
from dcnn_tpu.models import create_model
from dcnn_tpu.optim import AdamW
from dcnn_tpu.train.trainer import Trainer, create_train_state
from dcnn_tpu.utils.env import get_env


def zipf_tokens(seed: int, n: int, length: int, vocab: int,
                exponent: float = 1.0) -> np.ndarray:
    """``[n, length]`` int32 ids by Zipf's law: id ``r`` is drawn with
    probability proportional to ``(r + 1) ** -exponent``, uneven as text is,
    so that expert routing is uneven too."""
    rng = np.random.default_rng(seed)
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -float(exponent)
    cdf = np.cumsum(p / p.sum())
    ids = np.searchsorted(cdf, rng.random((n, length)))
    return np.minimum(ids, vocab - 1).astype(np.int32)


def build(cfg, model) -> Trainer:
    """The trainer proper: AdamW as the family trains (beta2 0.95, decay
    0.1) at a constant learning rate, and the token cross-entropy."""
    opt = AdamW(cfg.learning_rate, beta2=get_env("ADAM_BETA2", 0.95),
                weight_decay=get_env("WEIGHT_DECAY", 0.1))
    return Trainer(model, opt, "token_crossentropy", cfg)


def main():
    import jax

    cfg = setup("lm_trainer")
    model = create_model(get_env("MODEL", "deepseek_v2_lite_ep8"))
    print(model.summary())
    tokens = zipf_tokens(cfg.seed, get_env("TRAIN_SEQUENCES", 32),
                         get_env("SEQ_LEN", 4096) + 1, model.vocab)
    train = TokenDataset(tokens, model.vocab, batch_size=cfg.batch_size)
    print(f"input: HBM-resident ({train.hbm_bytes / 1e6:.1f} MB of token ids, "
          f"{train.steps_per_epoch} steps of {cfg.batch_size} x {train.seq_len} an epoch)")
    trainer = build(cfg, model)
    ts = create_train_state(model, trainer.optimizer, jax.random.PRNGKey(cfg.seed))
    trainer.fit(ts, train)


if __name__ == "__main__":
    main()
