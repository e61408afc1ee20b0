"""The ``train`` job for a cell that is sized for device residency, refused
within a minute by a program that would not keep the table resident.

``jobs/train.py`` checks ``gbdt.residency`` against the configuration's
``expect.residency`` only after the whole table is generated, binned and
placed: minutes of set-up at 400,000 x 2,000, more than a run's time limit
for a program whose bin finding walks every distinct value in Python (the
parent of PR 30: 0.16 s a column). This job asks first, with a probe: the
cell's own width, bins, leaves and parameters on one full histogram chunk of
rows (32,768, or all rows if fewer), bins found on 2,000 of them. The terms
of the program's pre-flight estimate that do not grow with the rows (the
one-hot operand of a chunk, the accumulator, the histogram cache) are the
full-size ones at that many rows, and they are what decides residency for a
wide table: a program whose estimate exceeds the device chooses
``tpu_residency=stream`` for the probe as it would for the table. Then the
run ends here, non-zero, before the table exists. Otherwise the probe is
freed and ``jobs/train.py`` runs, unchanged.

The probe exists for ONE comparison: PR 30's parent, which has to fail
cleanly in this cell. Every later parent keeps this width resident, so the
probe is then work no run needs: the next ``benchmark`` PR points
``traffic/train-wide.json``'s ``job`` at ``train`` and deletes this file
(ROADMAP Queue 1). Until then its seconds are in the result's
``info.setup_parts_s.probe``, so ``setup_s`` can be read without them.
``memory_peak`` and ``refer`` are forwarded because ``rehearse.py`` and
``tests/readings.py`` reach for them on whatever job a cell names.
"""
import gc
import importlib.util
import os
import time

from lib import datagen

PROBE_ROWS = 32768
PROBE_BIN_SAMPLE = 2000


def _load_train():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "train.py")
    spec = importlib.util.spec_from_file_location("job_train", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


train = _load_train()
refer = train.refer
# rehearse.py and the tests put their own in its place on the CPU, which
# keeps no peak_bytes_in_use; run() hands whatever stands here to train.py
memory_peak = train.memory_peak


def probe(ctx: dict) -> None:
    """Exit non-zero unless the program keeps a probe of the cell's width
    in the residency the configuration expects."""
    import lightgbm_tpu as lgb
    cfg = ctx["config"]
    rows = min(int(cfg["data"]["rows"]), PROBE_ROWS)
    X, y = datagen.generate(cfg["data"], rows, ctx["seed"])
    params = dict(cfg["params"], bin_construct_sample_cnt=PROBE_BIN_SAMPLE)
    bst = lgb.Booster(params=params, train_set=lgb.Dataset(X, label=y, params=params))
    gbdt = bst._gbdt
    got, want = gbdt.residency, cfg["expect"]["residency"]
    ctx["log"](f"probe: {rows} x {X.shape[1]} rows, residency={got} "
               f"chunk={gbdt.spec.chunk_rows}")
    bst.free_dataset()
    del bst, gbdt, X, y
    gc.collect()
    if got != want:
        raise SystemExit(
            f"benchmarks/jobs/train_resident.py: the program chooses residency "
            f"{got!r} for {rows} rows of this cell's width; the cell is sized "
            f"for {want!r}. Refused before the table is built.")


def run(ctx: dict) -> dict:
    t0 = time.time()
    probe(ctx)
    probe_s = time.time() - t0
    train.memory_peak = memory_peak
    result = train.run(ctx)
    result["info"]["setup_parts_s"]["probe"] = probe_s
    return result
