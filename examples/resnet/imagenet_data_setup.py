"""One-time ImageNet data prep: JPEG TFRecords -> raw uint8 TFRecords.

The reference trains from TF-official ImageNet TFRecords and decodes
JPEG inside tf.data's C++ threadpool; this framework's feeder tasks are
python processes where PIL decode is GIL-bound (~700 img/s measured —
far below the chip's appetite).  The TPU-shaped answer mirrors the
reference's mnist_data_setup pattern (reference
examples/mnist/mnist_data_setup.py:41-65): decode ONCE, in parallel
across engine executor processes (one task per shard), and train from
fixed-size raw uint8 records that feed at memory speed through the
columnar fast path.

    python examples/resnet/imagenet_data_setup.py \
        --input_dir /data/imagenet-jpeg-tfr --output_dir /data/imagenet-raw \
        --image_size 224 --num_executors 8

Input shards may use either layout this repo's loader understands:
TF-official ("image/encoded" JPEG/PNG bytes + "image/class/label",
1-based) or this repo's writers ("image" bytes + "label").  Output
shards are always ("image" raw uint8 HWC bytes, "label" 0-based int),
one output shard per input shard, written with the native TFRecord
codec — `resnet_imagenet_spark.py --data_dir <output_dir>` then skips
decode entirely.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def convert_shard(in_path, out_path, image_size):
    """Decode one input shard to fixed-size raw records (runs inside an
    executor task; returns (records, skipped)).  Record validity is
    decided by the SAME helper the training example uses
    (imagenet_records.decode_record); invalid records are skipped and
    counted, never silently written with default labels or raw-baked
    compressed bytes."""
    import imagenet_records

    from tensorflowonspark_tpu import recordio

    n = skipped = 0
    with recordio.TFRecordWriter(out_path) as w:
        for rec in recordio.TFRecordReader(in_path):
            # decode_example: {name: (kind, values)}
            feats = {k: v for k, (_kind, v)
                     in recordio.decode_example(rec).items()}
            try:
                arr, label = imagenet_records.decode_record(
                    feats, image_size)
            except (KeyError, ValueError) as e:
                if skipped < 3:
                    print(f"  skipping record in "
                          f"{os.path.basename(in_path)}: {e}", flush=True)
                skipped += 1
                continue
            w.write(recordio.encode_example({
                "image": ("bytes", [arr.tobytes()]),
                "label": ("int64", [int(label)]),
            }))
            n += 1
    return n, skipped


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--input_dir", required=True)
    p.add_argument("--output_dir", required=True)
    p.add_argument("--image_size", type=int, default=224)
    p.add_argument("--num_executors", type=int, default=4)
    args = p.parse_args()

    from tensorflowonspark_tpu.dfutil import _part_files
    from tensorflowonspark_tpu.engine import LocalEngine

    files = _part_files(args.input_dir)
    os.makedirs(args.output_dir, exist_ok=True)
    jobs = [(f, os.path.join(args.output_dir, os.path.basename(f)))
            for f in files]

    def run_partition(it):
        return [(os.path.basename(src),) + convert_shard(
            src, dst, args.image_size) for src, dst in it]

    try:  # under spark-submit: the real cluster does the decode
        from pyspark import SparkContext

        from tensorflowonspark_tpu.engine import SparkEngine

        engine = SparkEngine(SparkContext.getOrCreate())
    except ImportError:
        engine = LocalEngine(args.num_executors)
    try:
        ds = engine.parallelize(jobs, min(len(jobs), args.num_executors * 2))
        results = ds.map_partitions(run_partition).collect()
    finally:
        engine.stop()
    total = sum(r[1] for r in results)
    skipped = sum(r[2] for r in results)
    for name, n, sk in sorted(results):
        print(f"  {name}: {n} records" + (f" ({sk} skipped)" if sk else ""))
    print(f"wrote {total} raw {args.image_size}px records in "
          f"{len(results)} shard(s) under {args.output_dir}"
          + (f"; skipped {skipped}" if skipped else ""))


if __name__ == "__main__":
    main()
