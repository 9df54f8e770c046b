"""Readings that set a cell's check limits: the program's, and the
control's, over many seeds in one process that owns the chip.

    python3 chipbench/control.py --workload <cell> --seeds 1-12 [--seconds 5]

For each seed it makes the cell's set-up, drives a short window at the
cell's own load, and compares a sample of the answers with the float32
reference exactly as a run does (``check.readings``). It then puts the
control in the program's place: the same reference computed one
precision step below what the configuration states (its ``check.control``:
the tower's matmul operands rounded to float8 e4m3, and the stored rows
rounded one step down, fp8 for bf16 rows, int4 for int8 rows), answering
the same sampled requests from its own routes, and reads the same numbers
for it. One JSON line per seed. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent


def seed_list(text):
    out = []
    for part in text.split(","):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b) + 1)) if b else [int(a)]
    return out


def window_answers(su, seconds):
    """Drive a window and return (rows answered, ids, scores)."""
    from chipbench import system
    mix = su.mix
    if su.server is not None:
        t_open = time.perf_counter() + 0.01
        done = system.serve_window(su.server, su.req, t_open=t_open)
        rows = [i for i, a in enumerate(done["answer"])
                if isinstance(a, tuple)]
        ids = np.stack([done["answer"][i][0] for i in rows])
        scores = np.stack([done["answer"][i][1] for i in rows])
        return np.asarray(rows), ids, scores
    calls, _ = system.bulk_window(su.search, su.req, mix, seconds=seconds)
    b = mix["call_batch"]
    rows = np.concatenate([np.arange(j * b, (j + 1) * b) for j, _, _ in calls])
    return (rows, np.concatenate([c[1] for c in calls]),
            np.concatenate([c[2] for c in calls]))


def readings_for_seed(jax, config, mix, seed, seconds):
    from chipbench import check, harness
    su = harness.Setup(jax, config, mix, seed=seed, seconds=seconds)
    rows, ids, scores = window_answers(su, seconds)
    su.free_program()
    pick = check.sample(len(rows), mix["check_sample"], su.r_check)
    ref = su.reference(rows[pick])
    program = check.readings(ref, ids[pick], scores[pick], cr=mix["cr"])
    ctrl = config["check"]["control"]
    low = su.reference(rows[pick], precision=ctrl["tower"],
                       lower=ctrl["rows"])
    c_ids, c_scores = low.answers(cr=mix["cr"])
    control = check.readings(ref, c_ids, c_scores, cr=mix["cr"])
    for arr in su.buffers.values():
        if isinstance(arr, jax.Array):
            arr.delete()
    return {"seed": seed, "answered": int(len(rows)),
            "checked": int(len(pick)), "program": program,
            "control": control}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from chipbench import device, harness
    harness.use_compile_cache(jax, ROOT)
    _, cell, config, mix, _, _ = harness.cell_spec(ROOT, args.workload)
    device.require(jax, cell["chips"])
    for seed in seed_list(args.seeds):
        print(json.dumps(readings_for_seed(jax, config, mix, seed,
                                           args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
