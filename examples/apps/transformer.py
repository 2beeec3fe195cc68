"""Transformer encoder app (BASELINE.json config 5, BERT-base at published
width) with optional strategy search:
flexflow-tpu transformer.py --budget 500 -ll:tpu 8

``--num-layers N`` cuts the depth (never the width) for a short run."""

import sys

import numpy as np

import flexflow_tpu as ff
from flexflow_tpu.models.transformer import build_transformer


def top_level_task():
    cfg = ff.get_default_config()
    num_layers = 12
    if "--num-layers" in sys.argv:
        num_layers = int(sys.argv[sys.argv.index("--num-layers") + 1])
    model, tokens, logits = build_transformer(
        cfg, num_layers=num_layers, d_model=768, num_heads=12, d_ff=3072,
        seq_len=512, vocab_size=30522, num_classes=2)
    model.compile(ff.AdamOptimizer(alpha=1e-4),
                  ff.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                  [ff.METRICS_ACCURACY], final_tensor=logits)
    model.init_layers(seed=cfg.seed)
    n = cfg.batch_size * 8
    rng = np.random.default_rng(cfg.seed)
    x = rng.integers(0, 30522, (n, 512)).astype(np.int32)
    y = rng.integers(0, 2, (n, 1)).astype(np.int32)
    model.fit(x, y, epochs=cfg.epochs)
    return model


if __name__ == "__main__":
    model = top_level_task()
