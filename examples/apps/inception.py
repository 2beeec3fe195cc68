"""InceptionV3 app (reference examples/cpp/InceptionV3/inception.cc) — the
benchmark north-star workload."""

import flexflow_tpu as ff
from flexflow_tpu.data import synthetic_dataset
from flexflow_tpu.models.inception import build_inception_v3


def top_level_task():
    cfg = ff.get_default_config()
    model, inp, logits = build_inception_v3(cfg, num_classes=1000)
    model.compile(ff.SGDOptimizer(lr=cfg.learning_rate),
                  ff.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                  [ff.METRICS_ACCURACY], final_tensor=logits)
    model.init_layers(seed=cfg.seed)
    xs, y = synthetic_dataset(cfg.batch_size * 2, [inp.shape[1:]], (1,),
                              num_classes=1000)
    model.fit(xs[0], y, epochs=cfg.epochs)
    return model


if __name__ == "__main__":
    model = top_level_task()
