"""Random-effect scale-cliff probe.

Measures, across (entities, rows) points, where the host bucket build and
the device-resident fat tensors actually break:

- ``build_s``: RandomEffectDataset.build wall (host: counting sort, segment
  bounds, histogram shapes, native indices-only pass)
- ``host_mb``: bytes the host-resident dataset holds (index maps only — the
  compact path defers the (E,S,D) fills)
- ``fat_mb``: bytes the device-resident fat tensors would occupy in HBM at
  f32 / bf16 (the ``_materialize_fat`` product: x (E,S,D) + labels/weights
  (E,S))
- ``slots/rows``: padding inflation of the chosen bucketing

Run:  PYTHONPATH=/root/repo python tools/re_scaling_probe.py [--big]
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def gen(n, n_entities, d=8, seed=0):
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, n_entities + 1)
    p /= p.sum()
    ent = rng.choice(n_entities, size=n, p=p).astype(np.int64)
    xr = rng.normal(size=(n, d)).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    return xr, y, ent


def probe(n, n_entities, d=8):
    from photon_ml_tpu.game.data import (
        GameData,
        RandomEffectDataset,
        RandomEffectDatasetConfig,
    )
    from photon_ml_tpu.testing import dense_shard

    xr, y, ent = gen(n, n_entities, d)
    data = GameData.build(labels=y, shards={"re": dense_shard(xr)},
                          id_columns={"entityId": ent})
    cfg = RandomEffectDatasetConfig("entityId", "re",
                                    bucket_strategy="histogram",
                                    max_sample_buckets=5)
    from photon_ml_tpu.game.data import resident_fat_bytes

    t0 = time.perf_counter()
    ds = RandomEffectDataset.build("perEntity", data, cfg)
    build_s = time.perf_counter() - t0
    fat_f32 = resident_fat_bytes(ds.buckets)
    slots = host_b = 0
    for b in ds.buckets:
        e, s = b.sample_idx.shape
        slots += e * s
        host_b += b.sample_idx.nbytes + b.feature_index.nbytes
    n_active = sum(int((b.sample_idx >= 0).sum()) for b in ds.buckets)
    fat_bf16 = fat_f32 - sum(
        b.sample_idx.shape[0] * b.sample_idx.shape[1]
        * b.feature_index.shape[1] * 2 for b in ds.buckets)
    return dict(n=n, entities=n_entities, buckets=len(ds.buckets),
                build_s=round(build_s, 2),
                slots_over_rows=round(slots / max(n_active, 1), 2),
                host_mb=round(host_b / 1e6, 1),
                fat_f32_mb=round(fat_f32 / 1e6, 1),
                fat_bf16_mb=round(fat_bf16 / 1e6, 1))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--big", action="store_true",
                    help="include the 100M-row / 10M-entity point "
                         "(~12 GB host RAM, minutes)")
    args = ap.parse_args()
    points = [
        (1_000_000, 150_000),
        (10_000_000, 150_000),   # the bench point
        (10_000_000, 1_000_000),
        (10_000_000, 3_000_000),
        (30_000_000, 3_000_000),
    ]
    if args.big:
        points.append((100_000_000, 10_000_000))
    print(f"{'rows':>12} {'entities':>10} {'bkts':>5} {'build_s':>8} "
          f"{'pad×':>6} {'host_MB':>8} {'fat_f32_MB':>11} {'fat_bf16_MB':>12}")
    for n, e in points:
        r = probe(n, e)
        print(f"{r['n']:>12} {r['entities']:>10} {r['buckets']:>5} "
              f"{r['build_s']:>8} {r['slots_over_rows']:>6} "
              f"{r['host_mb']:>8} {r['fat_f32_mb']:>11} "
              f"{r['fat_bf16_mb']:>12}", flush=True)


if __name__ == "__main__":
    main()
