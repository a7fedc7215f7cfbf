"""ImageNet-shape input-pipeline throughput bench (VERDICT r2 missing #2:
prove decode+augment can feed the chip at its measured img/s).

Generates realistic synthetic JPEGs (~100-200KB, short side ~375, the
ImageNet file-size regime), packs them into .btr shards, then measures
RecordImageDataSet streaming throughput (decode + per-sample random
crop/flip + normalize + batch assembly) in train mode at 224x224.

    python scripts/input_pipeline_bench.py [n_images] [n_threads] [batch]

Prints one JSON line: images/sec plus the decode backend in use.
Reference bar: MTLabeledBGRImgToBatch.scala:48-133 kept Xeon clusters
saturated; our bar is >= the measured model img/s
(`git show 1f6203a:BENCH_r03.json`, another installation).

ISSUE 13 sweep mode — grid the executor pipeline and report per-config
stall fraction against a simulated device step:

    python scripts/input_pipeline_bench.py --sweep \
        --workers 1,2,4,8 --depths 1,2,4 --stages off,host,device \
        --stepMs 50 [--images N] [--batch B]

Each config prints one JSON line ({"metric": "pipeline_sweep", ...,
"stall_frac": ...}): the consumer "trains" for --stepMs per batch, and
stall_frac is the fraction of wall-clock it spent waiting on the feed —
0.0 means the executor kept the (simulated) chip fed.
"""

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def make_jpegs(root: str, n: int, seed: int = 0) -> None:
    from PIL import Image

    rs = np.random.RandomState(seed)
    d = os.path.join(root, "class0")
    os.makedirs(d, exist_ok=True)
    for i in range(n):
        # smooth gradients + mild noise compress to ~the ImageNet size
        # regime at q87; pure noise would be unrealistically large
        h = int(rs.randint(375, 500))
        w = int(rs.randint(480, 640))
        yy = np.linspace(0, 255, h)[:, None]
        xx = np.linspace(0, 255, w)[None, :]
        base = np.stack([yy + 0 * xx, 0 * yy + xx, (yy + xx) / 2], -1)
        img = (base + rs.randn(h, w, 3) * 28).clip(0, 255).astype(np.uint8)
        Image.fromarray(img).save(os.path.join(d, f"{i}.jpg"), quality=87)


def run(n_images: int = 512, n_threads: int = 16, batch: int = 128,
        epochs: int = 2):
    from bigdl_tpu.dataset import native
    from bigdl_tpu.dataset.recordfile import write_image_shards
    from bigdl_tpu.dataset.streaming import RecordImageDataSet

    with tempfile.TemporaryDirectory() as td:
        img_root = os.path.join(td, "imgs")
        make_jpegs(img_root, n_images)
        sizes = [os.path.getsize(os.path.join(img_root, "class0", f))
                 for f in os.listdir(os.path.join(img_root, "class0"))]
        shard_dir = os.path.join(td, "shards")
        write_image_shards(img_root, shard_dir, images_per_shard=256)

        ds = RecordImageDataSet(
            shard_dir, batch_size=batch, crop=(224, 224), train=True,
            short_side=256,
            mean=[123.68, 116.779, 103.939], std=[58.4, 57.1, 57.4],
            n_threads=n_threads, window=4)
        # warmup epoch fragment: imports, thread pool, reader handles
        next(iter(ds))
        t0 = time.perf_counter()
        n_done = 0
        for _ in range(epochs):
            for b in ds:
                n_done += b.input.shape[0]
        dt = time.perf_counter() - t0
        out = {
            "metric": "input_pipeline_imagenet_shape",
            "images_per_second": round(n_done / dt, 1),
            "n_images": n_images, "batch": batch,
            "n_threads": n_threads,
            "mean_jpeg_kb": round(float(np.mean(sizes)) / 1024, 1),
            "native_jpeg_decode": native.jpeg_available(),
            "seconds": round(dt, 2),
        }
        print(json.dumps(out), flush=True)
        return out


def sweep(n_images: int = 256, batch: int = 64, step_ms: float = 50.0,
          workers_list=(1, 2, 4, 8), depths=(1, 2, 4),
          stages=("off", "host", "device"), epochs: int = 2):
    """Grid dataWorkers x prefetchDepth x stage over the SAME shard set
    and report stall_frac against a simulated --stepMs device step.
    One JSON line per config (ISSUE 13 satellite)."""
    from bigdl_tpu.dataset import native
    from bigdl_tpu.dataset.pipeline import (EpochPlan, ExecutorDataSet,
                                            StagedDataSet,
                                            StreamingSampleSource)
    from bigdl_tpu.dataset.recordfile import write_image_shards
    from bigdl_tpu.dataset.streaming import RecordImageDataSet

    results = []
    with tempfile.TemporaryDirectory() as td:
        img_root = os.path.join(td, "imgs")
        make_jpegs(img_root, n_images)
        shard_dir = os.path.join(td, "shards")
        write_image_shards(img_root, shard_dir, images_per_shard=256)

        for stage in stages:
            for workers in workers_list:
                for depth in depths:
                    rds = RecordImageDataSet(
                        shard_dir, batch_size=batch, crop=(224, 224),
                        train=True, short_side=256,
                        mean=[123.68, 116.779, 103.939],
                        std=[58.4, 57.1, 57.4], n_threads=1, window=1)
                    src = StreamingSampleSource(rds)
                    plan = EpochPlan(len(src), batch, seed=rds.seed,
                                     shuffle=True, process_index=0,
                                     process_count=1)
                    ds = ExecutorDataSet(src, workers=workers,
                                         depth=depth, plan=plan)
                    if stage != "off":
                        ds = StagedDataSet(ds, stage=stage, depth=depth)
                    step_s = step_ms / 1000.0
                    # warm: thread spawn + first decode outside the clock
                    it = iter(ds)
                    next(it)
                    n_done = batch  # the warm batch still trains below
                    t0 = time.perf_counter()
                    time.sleep(step_s)  # "device step" for the warm batch
                    for _ in range(epochs):
                        for mb in it:
                            n_done += batch
                            time.sleep(step_s)  # simulated device step
                        ds.shuffle()
                        it = iter(ds)
                    dt = time.perf_counter() - t0
                    steps = n_done // batch
                    # the sleeps total steps*step_s; everything else in
                    # the wall clock is the feed making the consumer wait
                    wait_s = max(0.0, dt - steps * step_s)
                    out = {
                        "metric": "pipeline_sweep",
                        "workers": workers, "depth": depth, "stage": stage,
                        "batch": batch, "step_ms": step_ms,
                        "images_per_second": round(n_done / dt, 1),
                        "stall_frac": round(wait_s / dt, 4),
                        "seconds": round(dt, 2),
                        "native_jpeg_decode": native.jpeg_available(),
                    }
                    print(json.dumps(out), flush=True)
                    results.append(out)
    return results


def _parse_csv(s, cast):
    return tuple(cast(v) for v in s.split(",") if v)


if __name__ == "__main__":
    import argparse

    p = argparse.ArgumentParser(__doc__.splitlines()[0])
    p.add_argument("n_images", nargs="?", type=int, default=512)
    p.add_argument("n_threads", nargs="?", type=int, default=16)
    p.add_argument("batch_pos", nargs="?", type=int, default=None)
    p.add_argument("--sweep", action="store_true",
                   help="grid dataWorkers x prefetchDepth x stage "
                        "(executor pipeline) instead of the legacy "
                        "single-config window-feed bench")
    p.add_argument("--images", type=int, default=None)
    p.add_argument("--batch", type=int, default=None)
    p.add_argument("--stepMs", type=float, default=50.0,
                   help="simulated device step per batch for --sweep")
    p.add_argument("--workers", default="1,2,4,8")
    p.add_argument("--depths", default="1,2,4")
    p.add_argument("--stages", default="off,host,device")
    p.add_argument("--epochs", type=int, default=2)
    a = p.parse_args()
    if a.sweep:
        sweep(a.images or a.n_images or 256,
              a.batch or a.batch_pos or 64, a.stepMs,
              _parse_csv(a.workers, int), _parse_csv(a.depths, int),
              _parse_csv(a.stages, str), a.epochs)
    else:
        run(a.images or a.n_images, a.n_threads,
            a.batch or a.batch_pos or 128)
