"""A training cell: the watched job as its owner runs it, measured.

The job is the launcher's (``repro.launch.train``) own pieces at the
cell's sizes: ``parse_args``, ``build_model``, ``build_optimizer``,
``make_train_step`` compiled with donated state, ``TrainMonitor`` with the
compiled step registered, and ``Pipeline`` over the benchmark's seeded
token source. One step is what ``train.main``'s loop does:
``pipe.next()`` and a device put, the compiled step, ``float(loss)``,
``monitor.on_step``. A cell of more than one chip takes ``train.main``'s
sharded path: a ``make_local_mesh`` over the cell's chips, the model with
a ``ShardingCtx``, parameters and Adam's state placed by the program's
``param_shardings`` and the batch split under its ``batch`` axis.

Set-up builds that one object, drives it through the compared steps
(which also warm it up) and hands it to the window. In the window the
loop keeps about ``AHEAD_S`` seconds of steps dispatched ahead of the one
whose loss it reads, so that a host that stands still for a moment does
not leave the chip idle; each loss goes to the monitor when it is read.
When ``--seconds`` are up nothing more is dispatched, every step sent is
waited for, and the clock is read after that wait: the rate counts all
those steps over all that time. With ``--trace 1`` a few more steps run
the same way under the profiler, each part in a ``bench.*`` span. Then
the device's peak memory is read, the job's state is freed, and the
reference repeats the compared steps from the same seed.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from harness import compare, device, flops, layout, trace
from harness.traffic import TokenSource

# seconds of steps dispatched ahead of the step whose loss is read, and the
# most steps that may be in flight however short a step is
AHEAD_S = 5.0
AHEAD_MAX = 16

OPTIMIZER_KEYS = ("lr", "warmup_steps", "total_steps", "min_lr_frac", "b1",
                  "b2", "eps", "weight_decay", "clip_norm")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Compiles:
    """Backend compiles in this process from creation to ``close``: the
    window must have none."""

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, *_args, **_kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.count += 1

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)


def program_config(c: dict):
    """The program's registered architecture with the file's sizes."""
    from repro.configs import get_arch
    base = get_arch(c["program_arch"])
    fields = {f.name for f in dataclasses.fields(base)} - {"name"}
    return dataclasses.replace(base, **{k: v for k, v in c.items()
                                        if k in fields})


def _leaf_norms(fn: Callable) -> Callable:
    """Jitted per-leaf norms of ``fn(*trees)``, as a dict by leaf path."""
    def run(*trees):
        return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                for x in jax.tree_util.tree_leaves(fn(*trees))]
    jitted = jax.jit(run)

    def call(*trees):
        return dict(zip(layout.leaf_names(trees[0]),
                        (float(v) for v in jitted(*trees))))
    return call


class Job:
    """The monitored training job of one cell, from one seed."""

    def __init__(self, cell, seed: int, workdir: Path,
                 fault: Optional[str] = None):
        from repro.core import JobManifest, TrainMonitor
        from repro.data import Pipeline
        from repro.launch import train
        from repro.launch.mesh import make_local_mesh, mesh_num_chips
        from repro.optim.optimizer import OptState
        from repro.train import StepConfig, make_train_step
        from repro.train.sharding import ShardingCtx, param_shardings

        c, mix, t = cell.config, cell.traffic, cell.config["train"]
        self.c, self.mix, self.seed, self.fault = c, mix, seed, fault
        self.compiles = Compiles()
        self.tokens = mix["seq_len"] * mix["batch"]
        args = train.parse_args([
            "--arch", c["program_arch"], "--seq-len", str(mix["seq_len"]),
            "--batch", str(mix["batch"]), "--remat", t["remat"],
            "--lr", repr(t["lr"]), "--steps", str(t["total_steps"]),
            "--monitor-interval", repr(mix["monitor_interval_s"]),
            "--model-axis", str(t.get("model_axis", 1)),
            "--workdir", str(workdir)])
        cfg = program_config(c)
        chips = cell.workload["chips"]
        mesh = ctx = None
        if chips > 1:
            mesh = make_local_mesh(args.model_axis)
            if mesh_num_chips(mesh) != chips:
                raise ValueError(f"the cell asks for {chips} chips; the "
                                 f"mesh spans {mesh_num_chips(mesh)}")
            ctx = ShardingCtx(mesh=mesh)
        model = train.build_model(cfg, args, ctx)
        optimizer = train.build_optimizer(args)
        ocfg = dataclasses.asdict(optimizer.cfg)
        wrong = {k: (ocfg[k], t[k]) for k in OPTIMIZER_KEYS
                 if ocfg[k] != t[k]}
        if wrong:
            raise ValueError(f"the launcher's optimizer differs from the "
                             f"configuration (program, file): {wrong}")
        self.b1 = t["b1"]
        manifest = JobManifest(
            job_id=f"bench.{cell.name}", user="bench", app=cfg.name,
            shape=f"seq{mix['seq_len']}xb{mix['batch']}", num_hosts=1,
            num_chips=chips,
            mesh_shape="{}" if mesh is None else str(dict(mesh.shape)),
            started_ts=time.time())
        self.monitor = TrainMonitor(workdir, manifest, host="host0000",
                                    interval_s=args.monitor_interval,
                                    enabled=not args.no_monitor)
        self.source = TokenSource(mix, c["vocab_size"], seed)
        sample = self.source.get(0)
        self.p_shard = opt_shard = self.batch_shard = None
        if ctx is not None:
            self.p_shard = param_shardings(
                jax.eval_shape(model.init, jax.random.PRNGKey(0)), ctx)
            opt_shard = OptState(NamedSharding(mesh, PartitionSpec()),
                                 self.p_shard, self.p_shard)
            self.batch_shard = {
                k: NamedSharding(mesh, ctx.spec(
                    ("batch",) + (None,) * (v.ndim - 1), v.shape))
                for k, v in sample.items()}
        t0 = time.perf_counter()
        self.params = layout.make_params(c, seed, self.p_shard)
        self.opt_state = jax.block_until_ready(
            jax.jit(optimizer.init, out_shardings=opt_shard)(self.params))
        t1 = time.perf_counter()
        self.pipe = Pipeline(self.source, stats=self.monitor.pipeline_stats)
        step_fn = make_train_step(model, optimizer, StepConfig())
        sample = jax.device_put(sample, self.batch_shard)
        self.compiled = jax.jit(
            step_fn, donate_argnums=(0, 1),
            out_shardings=(self.p_shard, opt_shard, None, None)).lower(
            self.params, self.opt_state, None, sample).compile()
        del sample
        t2 = time.perf_counter()
        self.monitor.register_compiled(self.compiled,
                                       tokens_per_step=self.tokens)
        self.phases = {"weights": t1 - t0, "compile": t2 - t1,
                       "register_compiled": time.perf_counter() - t2}
        self.grad_norms = _leaf_norms(
            lambda mu: jax.tree_util.tree_map(lambda m: m / (1 - self.b1),
                                              mu))
        self.change_norms = _leaf_norms(
            lambda p, p0: jax.tree_util.tree_map(
                lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                p, p0))
        self.n = 0
        self.ahead = 1
        self.annotate = False

    def _span(self, name: str):
        if self.annotate:
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def step(self) -> float:
        """One step of the launcher's loop: dispatched, and its loss read
        at once."""
        return self._finish(self._dispatch())

    def _dispatch(self):
        """The batch put on the device and the compiled step dispatched;
        returns the step's metrics, still on the device."""
        with self._span("bench.input"):
            host = self.pipe.next()
            if self.fault == "half_batch":
                host = dict(host, loss_mask=host["loss_mask"].copy())
                host["loss_mask"][host["loss_mask"].shape[0] // 2:] = 0.0
            batch = jax.device_put(host, self.batch_shard)
        with self._span("bench.dispatch"):
            before = self._fault_before()
            if self.fault == "no_exchange":
                metrics = self._step_without_exchange(host)
            else:
                self.params, self.opt_state, _, metrics = self.compiled(
                    self.params, self.opt_state, None, batch)
            self._fault_after(before)
        return metrics

    def _finish(self, metrics) -> float:
        """The step's loss read back and handed to the monitor."""
        with self._span("bench.loss_fetch"):
            loss = float(metrics["loss"])
        self.n += 1
        with self._span("bench.on_step"):
            self.monitor.on_step(self.n, loss=loss, tokens=self.tokens)
        return loss

    # planted faults, for the tests and the chip readings of faults
    def _fault_before(self):
        copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)
        if self.fault == "unchanged":
            return copy(self.params), copy(self.opt_state)
        if self.fault == "double_leaf":
            return jnp.copy(self.params["embed"]["table"])
        return None

    def _fault_after(self, before) -> None:
        if self.fault == "unchanged":
            self.params, self.opt_state = before
        elif self.fault == "double_leaf":
            new = self.params["embed"]["table"]
            self.params["embed"]["table"] = (2 * new.astype(jnp.float32)
                                             - before).astype(new.dtype)

    def _step_without_exchange(self, host) -> dict:
        """The step with the gradient's exchange between chips left out:
        each chip updates its share of the state from the gradient of its
        own rows alone. For each chip's rows, the compiled step runs from a
        copy of the state on those rows repeated over the whole batch, so
        that its mean gradient is theirs, and the chips that hold those rows
        keep their shards of the result."""
        if self.batch_shard is None:
            raise ValueError("no exchange between chips on one chip")
        b = host["tokens"].shape[0]
        rows: Dict[tuple, list] = {}
        for dev, idx in self.batch_shard["tokens"].devices_indices_map(
                host["tokens"].shape).items():
            rows.setdefault(idx[0].indices(b)[:2], []).append(dev)
        state = (self.params, self.opt_state)
        kept, losses = {}, []
        for (a, z), devs in rows.items():
            own = {k: np.concatenate([v[a:z]] * (b // (z - a)))
                   for k, v in host.items()}
            copy = jax.tree_util.tree_map(jnp.copy, state)
            p, o, _, metrics = self.compiled(
                *copy, None, jax.device_put(own, self.batch_shard))
            losses.append(float(metrics["loss"]))
            for i, x in enumerate(jax.tree_util.tree_leaves((p, o))):
                for sh in x.addressable_shards:
                    if sh.device in devs:
                        kept[i, sh.device] = np.asarray(sh.data)
            del copy, p, o
        flat, tree = jax.tree_util.tree_flatten(state)
        del state
        self.params, self.opt_state = jax.tree_util.tree_unflatten(tree, [
            jax.make_array_from_single_device_arrays(
                x.shape, x.sharding,
                [jax.device_put(kept.pop((i, sh.device)), sh.device)
                 for sh in x.addressable_shards])
            for i, x in enumerate(flat)])
        return {"loss": sum(losses) / len(losses)}

    def compared_steps(self) -> dict:
        """The first steps from the seed, as the comparison reads them: each
        loss, the per-leaf norms of the first gradient as the optimizer
        applies it (Adam's first moment after one step, over ``1 - b1``)
        and its elements at ``layout.sample_positions``, and the norms of
        the parameters' change after the last."""
        losses, grad, sample = [], {}, {}
        for k in range(self.mix["compared_steps"]):
            t0 = time.perf_counter()
            losses.append(self.step())
            self.ahead = max(1, min(AHEAD_MAX, int(np.ceil(
                AHEAD_S / (time.perf_counter() - t0)))))
            if k == 0:
                grad = self.grad_norms(self.opt_state.mu)
                sample = {name: v / (1 - self.b1) for name, v in
                          layout.sample_leaves(self.opt_state.mu,
                                               self.seed).items()}
        p0 = layout.make_params(self.c, self.seed, self.p_shard)
        change = self.change_norms(self.params, p0)
        del p0
        return {"losses": losses, "grad": grad, "grad_sample": sample,
                "change": change}

    def _run(self, seconds: float = float("inf"),
             steps: Optional[int] = None) -> list:
        """Steps with ``self.ahead`` of them dispatched ahead of the one
        whose loss is read, until ``seconds`` are up or ``steps`` are
        dispatched; then every step sent is waited for. Returns the
        seconds from the start at which each loss was read."""
        pending = collections.deque()
        reads, sent, t0 = [], 0, time.perf_counter()
        while (time.perf_counter() - t0 < seconds
               and (steps is None or sent < steps)):
            pending.append(self._dispatch())
            sent += 1
            if len(pending) > self.ahead:
                self._finish(pending.popleft())
                reads.append(time.perf_counter() - t0)
        while pending:
            self._finish(pending.popleft())
            reads.append(time.perf_counter() - t0)
        return reads

    def window(self, seconds: float) -> dict:
        n0, c0 = self.n, self.compiles.count
        reads = self._run(seconds)
        steps = self.n - n0
        return {"steps": steps, "seconds": reads[-1],
                "tokens": steps * self.tokens,
                "compiles": self.compiles.count - c0, "ahead": self.ahead,
                "step_s": [b - a for a, b in zip([0.0] + reads, reads)]}

    def traced(self, steps: int, trace_dir: str) -> dict:
        self.annotate = True
        jax.profiler.start_trace(trace_dir)
        try:
            with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
                self._run(steps=steps)
        finally:
            jax.profiler.stop_trace()
            self.annotate = False
        dev_events, host_spans, about = trace.read_xplane(trace_dir)
        log(f"trace: {about}")
        return trace.reduce(dev_events, host_spans)

    def close(self) -> None:
        self.pipe.close()
        self.monitor.stop()
        self.compiles.close()
        del self.params, self.opt_state, self.compiled


def reference_batches(cell, seed: int):
    src = TokenSource(cell.traffic, cell.config["vocab_size"], seed)
    return [{k: jnp.asarray(v) for k, v in src.get(k).items()}
            for k in range(cell.traffic["compared_steps"])]


def run(cell, seed: int, seconds: float, traced: bool, dev: dict,
        t_start: float, fault: Optional[str] = None) -> dict:
    workdir = Path(tempfile.mkdtemp(prefix="bench-job-"))
    try:
        t_job = time.perf_counter()
        job = Job(cell, seed, workdir, fault)
        t_steps = time.perf_counter()
        prog = job.compared_steps()
        setup_s = time.perf_counter() - t_start
        phases = dict(start=t_job - t_start, **job.phases,
                      compared_steps=time.perf_counter() - t_steps)
        log(f"set-up {setup_s:.3f}s: " + ", ".join(
            f"{k} {v:.3f}s" for k, v in phases.items()))
        log(f"compared-step losses {prog['losses']}")
        win = job.window(seconds)
        slow = sorted(enumerate(win["step_s"]), key=lambda x: -x[1])[:3]
        log(f"window: {win['steps']} steps in {win['seconds']:.4f}s, "
            f"{win['ahead']} dispatched ahead, "
            f"{win['compiles']} compiles; median step "
            f"{statistics.median(win['step_s']):.4f}s; slowest "
            + ", ".join(f"#{i} {t:.4f}s" for i, t in slow))
        red = {}
        if traced:
            red = job.traced(cell.traffic["traced_steps"],
                             str(workdir / "trace"))
        mem = device.memory_peak_bytes(cell.workload["chips"])
        job.close()
        del job
        gc.collect()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    t_ref = time.perf_counter()
    refmod = cell.reference()
    ref = refmod.run_steps(cell.config, cell.config["train"], seed,
                           reference_batches(cell, seed))
    log(f"reference {time.perf_counter() - t_ref:.3f}s; losses "
        f"{ref['losses']}")
    numbers, leaves = compare.gaps(prog, ref)
    log(f"numbers {numbers}; worst leaves {leaves}")
    correct, checks = compare.judge(numbers, cell.limits)

    flops_step = flops.train_step_flops(cell.config, cell.traffic["seq_len"],
                                        cell.traffic["batch"])
    reading = {"window": win, "trace": red, "flops_per_step": flops_step,
               "peak_flops": device.peak_flops(dev["kind"]),
               "chips": cell.workload["chips"]}
    metrics: Dict[str, dict] = {}
    if traced:
        for m in cell.per_layer:
            value = cell.metric_reader(m["name"]).read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = {"setup_s": setup_s,
               "train_tokens_per_s": win["tokens"] / win["seconds"]}
        for m in cell.end_to_end:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    out_dev = dict(dev, memory_peak_bytes=mem)
    result = {"correct": correct, "attempted": win["steps"], "failed": 0,
              "metrics": metrics, "device": out_dev}
    if traced:
        if red:
            out_dev.update(busy_s=red["busy_s"], window_s=red["window_s"])
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
    result["checks"] = checks
    return result
