"""Traffic of kind `train`: one optimizer of the program's, driven through
`optimize()` over seeded batches.

Set-up builds the model with the benchmark's seeded weights, the data set and
the optimizer the traffic file names, drives that one optimizer through its
first steps (step 1, then on to step 3 or to the end of the first fused
window: the reference later follows their losses, the first gradient, and the
parameters' change and the optimizer's buffer at their end), warms up every
program, and hands the same optimizer to the window. The window is one `optimize()` call that a clock trigger ends after
`--seconds`; the rate is every step of that call over all of its wall time.
"""

import gc
import shutil
import sys
import tempfile
import time

import numpy as np

import check
import harness

class _Losses:
    """A train summary that keeps each iteration's loss and asks for nothing
    else (a learning-rate scalar would cost a device round trip a step)."""

    def __init__(self, never):
        self.by_iteration = {}
        self._never = never

    def add_scalar(self, tag, value, iteration):
        if tag == "Loss":
            self.by_iteration[int(iteration)] = float(value)

    def get_summary_trigger(self, name):
        return None if name == "Loss" else self._never


class _Clock:
    """The window's end trigger: fires once `seconds` have passed since its
    first evaluation (and `min_steps` steps, for the warm-up). With
    `trace_seconds` it also starts the profiler that long before the end, and
    stops it as it fires."""

    def __init__(self, seconds, trace_seconds=0.0, trace_dir=None, spans=None,
                 min_steps=0):
        self.seconds, self.trace_seconds = seconds, trace_seconds
        self.min_steps, self.first_step = min_steps, None
        self.trace_dir, self.spans = trace_dir, spans
        self.t_first = None
        self.evaluated = []     # the host's clock at each evaluation
        self.traced = None      # the traced seconds: clock, steps and spans at both ends

    def __call__(self, state):
        import jax
        now = time.perf_counter()
        self.evaluated.append(now)
        if self.t_first is None:
            self.t_first, self.first_step = now, state["neval"]
        left = self.t_first + self.seconds - now
        if self.trace_seconds and self.traced is None and left <= self.trace_seconds:
            # the device's own record only. With the host tracer on, every
            # piece of the host-side relayout of a 308 MB window is an event:
            # a copy that takes 0.2 s takes 10 s, the device starves inside
            # the traced seconds, and the trace takes minutes to write and read
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
            self.traced = {"t0": time.perf_counter(), "step0": state["neval"],
                           "spans0": self.spans()}
        if left > 0 or state["neval"] - self.first_step < self.min_steps:
            return False
        if self.traced is not None and "t1" not in self.traced:
            self.traced.update(spans1=self.spans(), step1=state["neval"])
            jax.profiler.stop_trace()
            self.traced["t1"] = time.perf_counter()
        return True


def setup(cell, seed, devices, warm_up=True):
    """Everything before the window. Returns the state the window and the
    check need. `calibrate.py` reads the first steps alone: no warm-up."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu import Engine, optim
    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.sample import MiniBatch
    from bigdl_tpu.optim import Trigger

    cfg, traffic, mod = cell.config, cell.traffic, cell.config_mod
    fuse = int(traffic["fuse_steps"])
    laps, mark = {}, [time.perf_counter()]

    def lap(name):
        now = time.perf_counter()
        laps[name], mark[0] = round(now - mark[0], 2), now

    Engine.init(seed=int(seed) % (2 ** 31 - 1))
    Engine.set_compute_dtype(jnp.dtype(cfg["compute_dtype"]))
    batches = mod.make_batches(cfg, traffic, np.random.default_rng(int(seed)))
    minis = [MiniBatch(x, y) for x, y in batches]
    index = {id(m): i for i, m in enumerate(minis)}
    dataset = DataSet.array(minis)
    lap("data_s")
    model, criterion = mod.build(cfg, traffic)
    names = mod.names(cfg)
    model.set_params(harness.tree_from_names(
        model.get_params(), names, mod.make_weights(cfg, harness.seed_key(seed))))
    lap("model_and_weights_s")

    method = traffic["optim_method"]
    opt = getattr(optim, traffic["optimizer"]["class"])(
        model, dataset, criterion, **traffic["optimizer"]["args"])
    opt.set_optim_method(getattr(optim, method["class"])(**method["args"]))
    opt.set_fuse_steps(fuse)
    never = Trigger(lambda s: False, "never",
                    steps_fn=lambda s: Trigger.NEVER_IN_LOOP)
    losses = _Losses(never)
    opt.set_train_summary(losses)

    def order():
        return [index[id(m)] for m in dataset.data(train=False)]

    def slot_norms():
        return check.floats(check.leaf_norms(harness.names_from_tree(
            opt._final_ostate[cell.method.SLOT], names)))

    # step 1 alone (the program's rule too: a run's first step goes through
    # the per-step program): the optimizer's state then holds the first gradient
    opt.set_end_when(Trigger.max_iteration(1)).optimize()
    used = order()[:1]
    scale = cell.method.gradient_scale(method["args"])
    observed = {"grad1": {k: v / scale for k, v in slot_norms().items()}}
    # then on to step 3 or, where steps are fused, through the first fused
    # window: the window's own program makes steps 2 to 1 + fuse, and hands
    # back the parameters and the optimizer's state that the check reads
    last = 1 + max(2, fuse)
    opt.set_end_when(Trigger.max_iteration(last)).optimize()
    used += order()[:last - 1]
    observed["momentum"] = slot_norms()
    # on the host: the reference says over which elements the change is read
    observed["after"] = {k: np.asarray(v) for k, v in harness.names_from_tree(
        model.get_params(), names).items()}
    observed["losses"] = [losses.by_iteration[i] for i in range(1, last + 1)]
    opt.set_train_summary(None)
    lap("first_steps_s")
    # warm-up: the window's own trigger, briefly, so that every program and
    # the feed's steady state have run before the clock starts
    if warm_up:
        opt.set_end_when(Trigger(_Clock(0.0, min_steps=3 * fuse), "clock",
                                 steps_fn=lambda s: fuse))
        opt.optimize()
        lap("warm_up_s")
    return {"laps": laps, "opt": opt, "model": model, "dataset": dataset, "batches": batches,
            "used": used, "observed": observed, "fuse": fuse}


def window(cell, st, seconds, trace):
    """One `optimize()` call of `seconds`; returns steps, wall time and, for a
    traced run, the trace directory and what the clock noted."""
    from bigdl_tpu.obs import trace as spans
    from bigdl_tpu.optim import Trigger

    opt, fuse = st["opt"], st["fuse"]
    trace_dir = None
    clock = _Clock(seconds)
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        spans.configure(enabled=True, trace_dir=trace_dir)
        clock = _Clock(seconds, min(float(cell.traffic["trace_seconds"]), seconds),
                       trace_dir, spans.span_totals)
    opt.set_end_when(Trigger(clock, "clock", steps_fn=lambda s: fuse))
    first = opt.state["neval"]
    t0 = time.perf_counter()
    opt.optimize()
    wall = time.perf_counter() - t0
    return {"steps": opt.state["neval"] - first, "wall_s": wall, "t0": t0,
            "evaluated": clock.evaluated, "trace_dir": trace_dir, "traced": clock.traced}


def observable_state(opt, model):
    """Every scalar leaf of the model's state that the program names as worth
    watching (`Optimizer.OBSERVABLE_STATE_LEAVES`), as the last step left it,
    by its path; empty for a model that has none. One fetch, outside any
    window."""
    import jax
    found = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(model.get_state())[0]:
        keys = [str(getattr(k, "key", k)) for k in path]
        if keys[-1] in opt.OBSERVABLE_STATE_LEAVES and getattr(leaf, "shape", None) == ():
            found["/".join(keys)] = leaf
    return {k: float(v) for k, v in jax.device_get(found).items()}


def over_the_traffics_bounds(cell, state):
    """A line for each leaf of `state` that reads over what the traffic file
    allows it (`state_at_most`, by the leaf's name): the window then measured
    something else than the cell, as when a routed layer repeats its pass."""
    most = cell.traffic.get("state_at_most", {})
    return [f"{cell.name}: after the window's last step the state leaf {path} reads "
            f"{value:g}, over the traffic file's state_at_most "
            f"{most[path.rsplit('/', 1)[-1]]:g}: not a sound measurement of the cell"
            for path, value in state.items()
            if value > most.get(path.rsplit("/", 1)[-1], float("inf"))]


def release(st, programs=True):
    """Drop the program's state, and its compiled programs unless a
    calibration goes on to another seed, so that the reference has the chip."""
    import jax
    for k in ("opt", "model", "dataset"):
        st.pop(k, None)
    gc.collect()
    if programs:
        jax.clear_caches()


def verify(cell, seed, st, observed=None, **fault):
    """The reference's readings for the batches the program's first steps
    used. With `observed`, the program's readings, their change is read here
    over the elements the reference names. `fault` (`q`, `rows`, `frozen`,
    with the reference's `mask`) reads the control or a planted fault in the
    program's place."""
    weights = cell.config_mod.make_weights(cell.config, harness.seed_key(seed))
    batches = [st["batches"][i] for i in st["used"]]
    want = check.follow(cell.reference(), cell.method, cell.config, cell.traffic,
                        weights, batches, **fault)
    if observed is not None and "after" in observed:
        observed["change"] = check.floats(check.change_norms(
            observed.pop("after"), weights, want["mask"]))
    return want


def run(cell, seed, seconds, trace, t_start, devices, peak):
    st = setup(cell, seed, devices)
    setup_s = time.perf_counter() - t_start
    compiles = harness.CompileCounter()
    with compiles:
        win = window(cell, st, seconds, trace)
    batch = cell.traffic["batch"]
    device = harness.device_report(devices, cell.chips)
    attempted = win["steps"]
    values = {"train_samples_per_s": win["steps"] * batch / win["wall_s"],
              "setup_s": setup_s}
    extra = {"steps": win["steps"], "window_s": win["wall_s"],
             "setup_laps": st["laps"]}
    # the host's view of where the window's time went: seconds into the call
    # at each evaluation of the end trigger (after every dispatch, and at an
    # epoch's end after the flush that waits for the device)
    extra["evaluated_s"] = [round(t - win["t0"], 3) for t in win["evaluated"]]
    win["state"] = observable_state(st["opt"], st["model"])
    if win["state"]:
        extra["state"] = win["state"]
    for line in over_the_traffics_bounds(cell, win["state"]):
        print(line, file=sys.stderr)
    observed = st["observed"]
    release(st)
    per_layer = {}
    if trace:
        import trace_reduce
        t_red = time.perf_counter()
        try:
            per_layer = trace_reduce.per_layer(cell, win, device, peak)
        finally:
            shutil.rmtree(win["trace_dir"], ignore_errors=True)
        extra["trace_reduce_s"] = round(time.perf_counter() - t_red, 2)
    t_ref = time.perf_counter()
    numbers = check.compare(observed, verify(cell, seed, st, observed))
    extra["reference_s"] = round(time.perf_counter() - t_ref, 2)
    extra["total_s"] = round(time.perf_counter() - t_start, 2)
    # a program compiled inside the window is a fault of the run, held to 0
    numbers["compiles_in_window"] = compiles.count
    limits = dict(cell.limits(), compiles_in_window=0)
    ok, compared = check.judge(numbers, limits)
    extra["not_judged"] = {k: v for k, v in numbers.items() if k not in limits}
    # `failed` counts steps of the window that did not complete: a step that
    # fails (a loss that is not finite, a lost device) raises out of
    # `optimize()`, and the run then prints no result at all
    return {"correct": ok, "attempted": attempted, "failed": 0,
            "values": values, "device": device, "extra": extra,
            "per_layer": per_layer, "compared": compared}
