#!/usr/bin/env python3
"""Readings that a training cell's limits are set from, on the chip.

    python3 bench/calibrate.py --workload mamba2-780m.train \
        --seeds 101-112 --control 3 --faults 3 [--out chiprun_out/x.jsonl]

In one process, for each seed: the program's compared steps (the same
``Job`` a run drives) against the reference: the lower readings. For the
first ``--control`` seeds, the control (the reference in fp8) against the
reference: the upper readings. For the first ``--faults`` seeds, the
program with a planted fault (half of the batch left out; one leaf's
update applied twice; on more than one chip, the gradient's exchange
between chips left out) against the reference. One JSON line per reading;
the benchmark's runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def seeds(spec: str):
    out = []
    for part in spec.split(","):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b) + 1)) if b else [int(a)]
    return out


def per_leaf(prog: dict, ref: dict) -> dict:
    """Each leaf's norms, program and reference, and the relative distance
    of its sampled first gradient, for the look at a gap."""
    out = {}
    for k in ref["grad"]:
        r = ref["grad_sample"][k]
        gap = (float(np.linalg.norm(prog["grad_sample"][k] - r))
               if k in prog["grad_sample"] else None)
        norm = float(np.linalg.norm(r))
        out[k] = {"grad": [prog["grad"].get(k), ref["grad"][k]],
                  "change": [prog["change"].get(k), ref["change"][k]],
                  "diff": None if gap is None else gap / max(norm, 1e-30),
                  "sample": [gap, norm]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    from harness import cells, compare, device
    from harness.train_cell import Job, reference_batches
    cell = cells.find(args.workload)
    device.check(cell.workload["chips"])
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    refmod = cell.reference()
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    def program(seed, fault=None):
        work = Path(tempfile.mkdtemp(prefix="bench-cal-"))
        try:
            job = Job(cell, seed, work, fault)
            r = job.compared_steps()
            job.close()
            del job
            gc.collect()
            return r
        finally:
            shutil.rmtree(work, ignore_errors=True)

    for i, seed in enumerate(seeds(args.seeds)):
        t = time.perf_counter()
        prog = program(seed)
        t_prog = time.perf_counter() - t
        batches = reference_batches(cell, seed)
        t = time.perf_counter()
        ref = refmod.run_steps(cell.config, cell.config["train"], seed,
                               batches)
        t_ref = time.perf_counter() - t
        nums, where = compare.gaps(prog, ref)
        emit({"kind": "program", "seed": seed, **nums, "leaves": where,
              "losses": prog["losses"], "ref_losses": ref["losses"],
              "per_leaf": per_leaf(prog, ref),
              "program_s": t_prog, "reference_s": t_ref,
              "seq_len": cell.traffic["seq_len"]})
        del prog
        if i < args.control:
            t = time.perf_counter()
            ctl = refmod.run_steps(cell.config, cell.config["train"], seed,
                                   batches, low=True)
            t_ctl = time.perf_counter() - t
            nums, where = compare.gaps(ctl, ref)
            emit({"kind": "control", "seed": seed, **nums, "leaves": where,
                  "losses": ctl["losses"], "per_leaf": per_leaf(ctl, ref),
                  "control_s": t_ctl})
            del ctl
        if i < args.faults:
            faults = ("half_batch", "double_leaf") + (
                ("no_exchange",) if cell.workload["chips"] > 1 else ())
            for fault in faults:
                fp = program(seed, fault)
                nums, where = compare.gaps(fp, ref)
                emit({"kind": fault, "seed": seed, **nums, "leaves": where,
                      "per_leaf": per_leaf(fp, ref)})
        del batches, ref
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
