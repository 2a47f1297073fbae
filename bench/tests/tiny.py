"""A run of a cell at a small size on the CPU, for the tests."""

import contextlib
import importlib.util
import io
import json

from harness import cells

TINY = {"mamba2-780m": dict(num_layers=2, d_model=64, vocab_size=97,
                            ssm_state=16, ssm_headdim=16, ssm_chunk=16),
        "hymba-1.5b": dict(num_layers=3, d_model=64, vocab_size=97,
                           num_heads=4, num_kv_heads=2, head_dim=16,
                           d_ff=96, window_size=24, ssm_state=8,
                           ssm_headdim=16, ssm_chunk=16, num_meta_tokens=4)}
TINY["hymba-1.5b-32l"] = dict(TINY["hymba-1.5b"], num_layers=4)
TRAFFIC = {"seq_len": 64, "batch": 4, "monitor_interval_s": 0.5}
# Limits read at these sizes where the cell's own were read at its size:
# here the sound program's loss_gap reads 1.7e-4 to 4.5e-4 and the fp8
# control's 1.8e-3 to 5.5e-3 (CPU, six seeds); at its own size the
# four-chip cell reads up to 1.05e-4 and takes the limit 2e-4.
LIMITS = {"hymba-1.5b-32l.train-4chip": {"loss_gap": 1e-3}}


def fake_device(chips):
    return {"platform": "cpu", "kind": "TPU v5 lite", "count": chips}


def load_run():
    spec = importlib.util.spec_from_file_location(
        "bench_run", cells.BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run(workload, seed=4294967311, trace=0, fault=None, check=fake_device):
    """(exit code, parsed last line or None, standard output)."""
    config = workload.rsplit(".", 1)[0]
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = load_run().main(
            ["--workload", workload, "--seed", str(seed), "--seconds", "1",
             "--trace", str(trace)],
            device_check=check, config_update=TINY[config],
            traffic_update=TRAFFIC, limits_update=LIMITS.get(workload),
            fault=fault)
    lines = out.getvalue().strip().splitlines()
    last = json.loads(lines[-1]) if rc == 0 and lines else None
    return rc, last, out.getvalue()
