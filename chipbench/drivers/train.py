"""Driver for training cells: the program's own ``Trainer.run``, timed from outside.

One :class:`~repro.launch.train.Trainer` is built and run once.  The
benchmark gives it the seed's weights (through ``initialize``) and its
batches (as ``trainer.data``), and watches the loop from the feed, which the
loop calls once before every step:

* before step 2 it reads the first gradient from the AdamW moments; before
  step ``check_steps + 1`` the change of the parameters so far;
* before step ``warmup_steps + 1`` it waits for the device (set-up ends) and
  opens the window;
* at the first call after ``--seconds`` have passed it waits for the device
  again, closes the window and sets ``job.steps`` to 0, so the loop ends.

The window counts the steps the device completed between those two waits.
With ``--trace 1`` a profiler trace covers the window.  After the run the
program's state is freed and the reference repeats the first steps.
"""

from __future__ import annotations

import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from chipbench.harness import BenchError, Context, Outcome

#: ``job.steps`` while the loop runs: more than any window takes.  The
#: learning-rate schedule's cosine spans it, so it stays in warm-up or flat.
ENDLESS = 10**9
#: The least share of the traced device time whose ops find a scope path in
#: :func:`step_hlo`'s program.  Below it that copy of the train step no longer
#: matches the one the window ran, and the per-scope readers would read low.
MIN_SCOPED_SHARE = 0.9


def model_config(model: dict):
    from repro.configs.base import ModelConfig

    return ModelConfig(**{k: tuple(v) if isinstance(v, list) else v for k, v in model.items()})


def seed_key(seed: int):
    """A PRNG key from a seed of any size."""
    import jax

    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def batch_maker(B: int, S: int, V: int):
    """-> jitted make(key, step): the batch of one step, token ids uniform over
    the vocabulary, every row different, labels the next token."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key, step):
        t = jax.random.randint(jax.random.fold_in(key, step), (B, S + 1), 0, V, jnp.int32)
        return {"tokens": t[:, :-1], "labels": t[:, 1:], "loss_mask": jnp.ones((B, S), jnp.float32)}

    return make


def step_hlo(program_model, job, weights, wkey, make_batch, dkey, dev) -> str:
    """The optimized HLO of the train step as ``Trainer`` builds it, for the
    op names of the trace.  The program is the one the window ran, so it comes
    back from the persistent compilation cache rather than compiling again."""
    import jax

    from repro.launch.steps import make_train_step
    from repro.optim import AdamWConfig, adamw_init, cosine_schedule

    sh = jax.sharding.SingleDeviceSharding(dev)

    def placed(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), tree)

    params = jax.eval_shape(weights, wkey)
    lr_fn = cosine_schedule(job.lr, warmup_steps=job.warmup, total_steps=ENDLESS)
    step = jax.jit(make_train_step(program_model, lr_fn, AdamWConfig(), grad_accum=job.grad_accum),
                   donate_argnums=(0, 1))
    args = (placed(params), placed(jax.eval_shape(adamw_init, params)), placed(jax.eval_shape(make_batch, dkey, 0)))
    return step.lower(*args).compile().as_text()


class CompileCounter:
    """Counts traces, compilations and cache reads, with the time of each."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration", "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")
    HIT, MISS = "/jax/compilation_cache/cache_hits", "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax

        self.times: list[tuple[float, str]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(lambda event, **_: self._on(event, 0.0))

    def _on(self, event, duration, **_):
        if event in self.EVENTS or event in (self.HIT, self.MISS):
            self.times.append((time.perf_counter(), event))

    def between(self, t0: float, t1: float, events=EVENTS) -> int:
        return sum(t0 <= t < t1 and e in events for t, e in self.times)


class Feed:
    """The batches of one seed, made on the device; calls ``hook(k)`` first."""

    def __init__(self, make, key, hook):
        self.make, self.key, self.hook = make, key, hook
        self.next_step = 0

    def __iter__(self):
        return self

    def __next__(self):
        k = self.next_step
        self.hook(k)
        self.next_step += 1
        return self.make(self.key, k)

    def state_dict(self) -> dict:
        return {"next_step": self.next_step}

    def load_state_dict(self, state: dict) -> None:
        self.next_step = int(state["next_step"])

    def close(self) -> None:
        pass


def run(ctx: Context) -> Outcome:
    import jax
    import jax.numpy as jnp

    from chipbench import compare
    from chipbench.peaks import peaks_for
    from chipbench.readings import Readings, breakdown, children_cpu_s, thread_cpu_s
    from chipbench.reference import layout
    from chipbench.reference.common import ADAMW, leaf_norms
    from chipbench.reference.train import run_reference
    from chipbench.trace import WINDOW_MARK, read_profile
    from repro.configs.base import list_archs, register
    from repro.launch.train import Trainer, TrainJobConfig
    from repro.optim import adamw_init

    tr, model = ctx.traffic, ctx.model
    B, S = int(tr["batch"]), int(tr["seq_len"])
    check_steps, warmup = int(tr["check_steps"]), int(tr["warmup_steps"])
    if warmup <= check_steps:
        raise BenchError("warmup_steps must exceed check_steps")
    window_len = min(ctx.seconds, float(tr.get("trace_seconds", ctx.seconds))) if ctx.trace else ctx.seconds
    name = ctx.config["name"]
    family = layout.family(ctx.config["reference"])
    cfg = model_config(model)
    list_archs()  # load the program's own configurations first, so this one is not overwritten
    register(name, lambda: cfg, lambda: cfg)
    counter = CompileCounter()

    key = seed_key(ctx.seed)
    wkey, dkey = jax.random.fold_in(key, 0), jax.random.fold_in(key, 1)
    weights = jax.jit(lambda k: layout.init_params(family, model, k))
    make_batch = batch_maker(B, S, cfg.vocab)

    norms = jax.jit(leaf_norms)
    change = jax.jit(lambda p, p0: leaf_norms(jax.tree.map(jnp.subtract, p, p0)))

    class BenchTrainer(Trainer):
        def initialize(self):
            want = jax.eval_shape(self.model.init, jax.random.key(0))
            params = weights(wkey)
            got = jax.eval_shape(lambda: params)
            if jax.tree.structure(want) != jax.tree.structure(got) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype)
                for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got), strict=True)
            ):
                raise BenchError(f"the program's parameter tree differs from the benchmark's layout: {want} != {got}")
            self.params = params
            self.opt_state = adamw_init(params)

    out_dir = tempfile.mkdtemp(prefix="chipbench-train-")
    trace_dir = os.path.join(out_dir, "trace")
    job = TrainJobConfig(
        arch=name, smoke=False, steps=ENDLESS, global_batch=B, seq_len=S,
        lr=float(tr["lr"]), warmup=int(tr["lr_warmup"]), seed=ctx.seed & 0x7FFFFFFF,
        out_dir=out_dir, ckpt_every=10**10, profile=True, profile_backend=tr["profile_backend"],
        sample_period_s=float(tr["sample_period_s"]), resume=False,
    )
    trainer = BenchTrainer(job)
    trainer.data.close()

    st: dict = {"prog": {}}

    def cpu_counters():
        return {"agent": thread_cpu_s("repro-profilerd-agent"), "daemon": children_cpu_s()}

    marks: dict[int, float] = {}

    def hook(k: int) -> None:
        if k <= warmup:
            marks[k] = time.perf_counter()
        if k == 1:
            st["prog"]["grad_norms"] = np.asarray(norms(trainer.opt_state["m"])) / (1.0 - ADAMW["b1"])
        if k == check_steps:
            st["prog"]["change_norms"] = np.asarray(change(trainer.params, weights(wkey)))
        if k == warmup:
            jax.block_until_ready(trainer.params)
            if ctx.trace:
                jax.profiler.start_trace(trace_dir)
                st["mark"] = jax.profiler.TraceAnnotation(WINDOW_MARK)
                st["mark"].__enter__()
            st["cpu0"] = cpu_counters()
            st["anomalies0"] = len(trainer.anomalies)
            st["steps0"], st["t_start"] = trainer.step, time.perf_counter()
        elif k > warmup and "t_end" not in st and time.perf_counter() - st["t_start"] >= window_len:
            jax.block_until_ready(trainer.params)
            st["steps1"], st["t_end"] = trainer.step, time.perf_counter()
            st["cpu1"] = cpu_counters()
            if ctx.trace:
                st["mark"].__exit__(None, None, None)
                jax.profiler.stop_trace()
            st["anomalies1"] = len(trainer.anomalies)
            trainer.job.steps = 0

    trainer.data = Feed(make_batch, dkey, hook)
    try:
        summary = trainer.run()
    finally:
        if ctx.trace and "mark" in st and "t_end" not in st:
            jax.profiler.stop_trace()
    if "t_end" not in st:
        raise BenchError("the training loop ended before the window closed")

    window_s = st["t_end"] - st["t_start"]
    steps = st["steps1"] - st["steps0"]
    losses = [m["loss"] for m in trainer.metrics_log]
    window_losses = losses[st["steps0"]:st["steps1"]]
    failed = sum(not math.isfinite(x) for x in window_losses)
    compiles = counter.between(st["t_start"], st["t_end"])
    anomalies = st["anomalies1"] - st["anomalies0"]
    print(f"window: {steps} steps in {window_s!r} s; compilations inside the window: {compiles}; "
          f"watchdog anomalies inside the window: {anomalies}; all anomalies: {summary.get('anomalies')}",
          file=sys.stderr, flush=True)
    st["prog"]["loss"] = losses[:check_steps]
    print(f"set-up: {marks[0] - ctx.t0!r} s to the first batch (imports, the trainer, weights, profiler), "
          f"{marks[1] - marks[0]!r} s for the first step (compile, device-plane costing, run), "
          f"{st['t_start'] - marks[1]!r} s for {warmup - 1} more warm-up steps", file=sys.stderr, flush=True)

    dev = jax.devices()[0]
    mem = dev.memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0)) + int(mem.get("peak_bytes_reserved", 0))
    print(f"memory: peak_bytes_in_use {mem.get('peak_bytes_in_use')!r}, "
          f"peak_bytes_reserved {mem.get('peak_bytes_reserved')!r}", file=sys.stderr, flush=True)

    end_to_end = {
        "setup_s": st["t_start"] - ctx.t0,
        "train_tokens_per_s": steps * B * S / window_s,
    }
    # Free the program's state before the trace is read and the reference runs.
    program_model = trainer.model
    trainer.params = trainer.opt_state = None
    del trainer, hook
    gc.collect()

    readings = bd = None
    if ctx.trace:
        t = time.perf_counter()
        hlo = step_hlo(program_model, job, weights, wkey, make_batch, dkey, dev)
        t1 = time.perf_counter()
        print(f"train-step HLO for the trace: {counter.between(t, t1, (counter.HIT,))} cache hits, "
              f"{counter.between(t, t1, (counter.MISS,))} misses, {t1 - t!r} s", file=sys.stderr, flush=True)
        trace = read_profile(trace_dir, [hlo])
        total = sum(e - s for s, e, *_ in trace.ops) or 1.0
        known = sum(e - s for s, e, _, path, _ in trace.ops if path)
        print(f"trace: {len(trace.ops)} device ops, {100 * known / total!r}% of their time with a scope path; "
              f"read in {time.perf_counter() - t1!r} s", file=sys.stderr, flush=True)
        if known / total < MIN_SCOPED_SHARE:
            raise BenchError(f"only {100 * known / total:.1f}% of the traced device time maps to a scope of the "
                             f"train step's HLO (at least {100 * MIN_SCOPED_SHARE:.0f}% is needed): "
                             "chipbench/drivers/train.py:step_hlo no longer builds the step the window ran")
        counters = {}
        for k in ("agent", "daemon"):
            a, b = st["cpu0"][k], st["cpu1"][k]
            counters[k] = None if a is None or b is None else b - a
        readings = Readings(trace, steps, steps * B * S, window_s, model, ctx.config["reference"], tr,
                            peaks_for(dev.device_kind), counters)
        bd = breakdown(trace)
        keep = os.environ.get("CHIPBENCH_KEEP_TRACE")
        if keep:  # the reduced trace and what its readers take, for the reader tests
            trace.save(keep)
            with open(keep.replace(".trace.json.gz", ".readings.json"), "w") as f:
                json.dump({k: getattr(readings, k) for k in
                           ("steps", "tokens", "window_s", "model", "reference", "traffic", "peaks", "counters")}, f)
    shutil.rmtree(out_dir, ignore_errors=True)

    batches = [make_batch(dkey, k) for k in range(check_steps)]
    hyper = {"lr": job.lr, "warmup": job.warmup, "total_steps": ENDLESS}
    t = time.perf_counter()
    ref = run_reference(ctx.config["reference"], model, weights(wkey), batches, hyper)
    print(f"reference: {check_steps} steps in {time.perf_counter() - t!r} s (compile {ref['compile_s']!r} s, "
          f"steps {ref['steps_s']!r} s)", file=sys.stderr, flush=True)
    checks = compare.checks(st["prog"], ref, tr["limits"])
    print(f"losses: program {st['prog']['loss']!r}, reference {ref['loss']!r}; "
          f"all numbers: {compare.numbers(st['prog'], ref)!r}", file=sys.stderr, flush=True)
    return Outcome(
        attempted=steps, failed=failed, end_to_end=end_to_end, checks=checks,
        memory_peak_bytes=peak, readings=readings, breakdown=bd,
    )
