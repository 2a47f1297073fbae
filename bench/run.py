#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 bench/run.py --workload mamba2-780m.train --seed 7 \
        --seconds 30 --trace 0

From the root of a checkout, on a machine that holds the chips the cell
asks for. The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device`` and, traced,
``breakdown``; ``checks`` comes last, each number compared with its limit.
The same numbers are the last lines of standard error. Without the
accelerator or the files the cell needs, the run exits non-zero and prints
no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, device_check=None, config_update=None,
         traffic_update=None, limits_update=None, fault=None) -> int:
    """``device_check``, ``config_update``, ``traffic_update``,
    ``limits_update`` and ``fault`` let the benchmark's own tests drive a
    run on the CPU at a small size, against limits read at that size,
    with a planted fault; a measured run leaves them unset."""
    args = parse_args(argv)
    from harness import cells, device
    try:
        cell = cells.find(args.workload)
        if not (BENCH.parent / "src" / "repro").is_dir():
            raise FileNotFoundError("the program's src/repro is missing")
    except (KeyError, FileNotFoundError, json.JSONDecodeError) as e:
        print(f"[bench] cannot run {args.workload}: {e}", file=sys.stderr)
        return 2
    cell.config.update(config_update or {})
    cell.traffic.update(traffic_update or {})
    cell.limits.update(limits_update or {})
    try:
        dev = (device_check or device.check)(cell.workload["chips"])
    except device.NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 3
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"[bench] {args.workload} seed {args.seed} on {dev}; compile "
          f"cache {cache}", file=sys.stderr, flush=True)
    kind = cell.traffic["kind"]
    if kind != "train_tokens":
        print(f"[bench] no driver for traffic kind {kind!r}",
              file=sys.stderr)
        return 2
    from harness import train_cell
    result = train_cell.run(cell, args.seed, args.seconds, bool(args.trace),
                            dev, T_START, fault)
    for name, chk in result["checks"].items():
        print(f"[bench] check {name} {chk['value']!r} limit {chk['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
