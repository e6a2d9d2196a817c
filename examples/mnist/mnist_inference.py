"""MNIST parallel inference: N independent single-node workers, no cluster
(parity: reference examples/mnist/keras/mnist_inference.py:79, which uses
TFParallel.run under Spark barrier scheduling).

Each worker loads the exported model, scores its shard of the TFRecords,
and writes a predictions file.

    python examples/mnist/mnist_data_setup.py --output /tmp/mnist
    python examples/mnist/mnist_tf.py            # produces the export
    python examples/mnist/mnist_inference.py \\
        --data_dir /tmp/mnist/tfr --export_dir /tmp/mnist_model_tf/export
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def infer_fun(args, ctx):
    import numpy as np

    from tensorflowonspark_tpu import recordio
    from tensorflowonspark_tpu.utils.checkpoint import load_exported

    params, meta = load_exported(args["export_dir"])
    import importlib

    mod, _, fn = meta["predict"].partition(":")
    predict = getattr(importlib.import_module(mod), fn)

    files = sorted(
        os.path.join(args["data_dir"], f)
        for f in os.listdir(args["data_dir"]) if f.startswith("part-")
    )[ctx.task_index::ctx.num_workers]

    os.makedirs(args["output"], exist_ok=True)
    out_path = os.path.join(args["output"], f"part-{ctx.task_index:05d}")
    n = 0
    with open(out_path, "w") as out:
        for path in files:
            images, labels = [], []
            for rec in recordio.TFRecordReader(path):
                feats = recordio.decode_example(rec)
                images.append(np.asarray(feats["image"][1], np.float32))
                labels.append(int(feats["label"][1][0]))
            if not images:
                continue
            res = predict(params, {"x": np.stack(images)})
            for lbl, pred in zip(labels, res["prediction"]):
                out.write(f"{lbl} {int(pred)}\n")
                n += 1
    return n


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--cluster_size", type=int, default=2)
    p.add_argument("--data_dir", default="/tmp/mnist/tfr")
    p.add_argument("--export_dir", default="/tmp/mnist_model_tf/export")
    p.add_argument("--output", default="/tmp/mnist_predictions")
    args = p.parse_args()

    from tensorflowonspark_tpu import configure_logging, parallel_run
    from tensorflowonspark_tpu.engine import LocalEngine

    configure_logging()
    engine = LocalEngine(
        args.cluster_size,
        # executors inherit the caller\'s platform; on the CPU each gets
        # one host device
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=1"},
    )
    counts = parallel_run.run(
        engine, infer_fun, vars(args), num_executors=args.cluster_size
    )
    engine.stop()
    print(f"wrote {sum(counts)} predictions to {args.output}")


if __name__ == "__main__":
    main()
