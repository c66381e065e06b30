"""How a routed cell's held pairs move, read on the chip at the cell's own
size. No benchmark run calls this; a `benchmark` PR does, when it chooses what
the cell feeds its optimizer (the rate, the seeded weights, the batches):

    python3 benchmarks/drift.py --workload <cell> --seeds 1,2,3 --steps 60 \
        [--variant '{"traffic": {"optim_method": {"args": {"learningrate": 1e-6}}}}' ...]
    python3 benchmarks/drift.py --workload <cell> --seeds 1,2,3 --survey [--variant ...]

A routed expert layer that is told which experts it holds works over a bound
on rows, twice the balanced expectation of held pairs, and repeats its pass
when a step's held pairs pass the bound: the step's time then follows the
routing. Without `--survey`, for each variant and seed, the cell's own
optimizer is driven `--steps` steps from the seeded weights and every step's
`pairs_held` (all layers' sum) and `row_passes` (the layers' largest), the
model's state leaves, are read with the loss; with `--layers` the reference
also counts each layer's held pairs (`reference/<config>.py`, `routing`)
under the parameters the last step left. With `--survey` no program runs: the
reference counts each layer's held pairs and distinct routings of every batch
at the seeded weights.
A `--variant` is a JSON object whose `config` and `traffic` are laid over
this tool's own copy of what the cell's files say; no run and no calibration
takes one. One JSON line a variant and seed on standard output.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [p for p in (HERE, os.path.dirname(HERE)) if p not in sys.path]

import harness  # noqa: E402


def _lay_over(base, over):
    """`over`'s entries put into `base`, a mapping inside a mapping entry by
    entry."""
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(base.get(k), dict):
            _lay_over(base[k], v)
        else:
            base[k] = v


# the state leaves of a routed model (`parallel/moe.py`) that this tool follows:
# the (token, expert) pairs that reached a held expert, all layers' sum, and
# the passes over its bound on rows that the fullest layer made for them
LEAVES = ("pairs_held", "row_passes")


class _State:
    """A train summary that keeps the observable state leaves of every step."""

    def __init__(self, never, leaves):
        self.by_leaf = {k: {} for k in leaves}
        self._never = never

    def add_scalar(self, tag, value, iteration):
        leaf = tag.rsplit("/", 1)[-1]
        if tag.startswith("State/") and leaf in self.by_leaf:
            self.by_leaf[leaf][int(iteration)] = float(value)

    def get_summary_trigger(self, name):
        return None if name.startswith("State/") or name == "Loss" else self._never


def _counter(cell):
    """`count(weights, batches)`: by the reference's routing, each layer's
    held pairs, a row a batch, and each layer's distinct routings (the sets
    of experts that a batch's positions reach); one compiled program for all
    of a variant's seeds."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    routing = jax.jit(lambda p, x: cell.reference().routing(p, x, cell.config))
    first, held = cell.config["held"]

    def count(weights, batches):
        pairs, distinct = [], []
        for x, _ in batches:
            with jax.default_matmul_precision("highest"):
                top_e = np.sort(np.asarray(routing(weights, jnp.asarray(x))), -1)
            top_e = top_e.reshape(top_e.shape[0], -1, top_e.shape[-1])
            pairs.append([int(((e >= first) & (e < first + held)).sum()) for e in top_e])
            distinct.append([len(np.unique(e, axis=0)) for e in top_e])
        return pairs, distinct
    return count


def _survey(cell, seed, count):
    """No program: each layer's held pairs and distinct routings of every
    batch at the seeded weights, how many positions each batch masks and how
    many distinct tokens it holds."""
    import numpy as np
    mod = cell.config_mod
    batches = mod.make_batches(cell.config, cell.traffic, np.random.default_rng(seed))
    weights = mod.make_weights(cell.config, harness.seed_key(seed))
    pairs, distinct = count(weights, batches)
    return {"masked": [int((x == cell.config["mask_token_id"]).sum()) for x, _ in batches],
            "tokens_distinct": [len(np.unique(x)) for x, _ in batches],
            "layers_seeded": pairs, "routings_distinct": distinct}


def _drive(cell, seed, devices, steps, count=None):
    """The cell's optimizer from the seeded weights through `steps` steps:
    the routing leaves after each (set-up drives the first steps, and the
    last of them is the first read); with `count`, each layer's held pairs
    under the parameters the last step left."""
    from bigdl_tpu.optim import Trigger
    st = cell.runner.setup(cell, seed, devices, warm_up=False)
    opt, model = st["opt"], st["model"]
    first = opt.state["neval"] - 1
    state = {path.rsplit("/", 1)[-1]: v
             for path, v in cell.runner.observable_state(opt, model).items()}
    never = Trigger(lambda s: False, "never", steps_fn=lambda s: Trigger.NEVER_IN_LOOP)
    seen = _State(never, LEAVES)
    opt.set_train_summary(seen)
    opt.set_end_when(Trigger.max_iteration(steps)).optimize()
    opt.set_train_summary(None)
    read = {k: {first: state[k], **seen.by_leaf[k]} for k in LEAVES}
    steps = sorted(next(iter(read.values())))
    out = {"losses_first_steps": st["observed"]["losses"], "steps": steps,
           **{k: [by_step[i] for i in steps] for k, by_step in read.items()}}
    if count is not None:
        out["layers_last"] = count(harness.names_from_tree(
            model.get_params(), cell.config_mod.names(cell.config)), st["batches"])[0]
    del opt, model
    cell.runner.release(st, programs=False)
    return out


def main(argv=None, bench_dir=harness.HERE, require_chip=True):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--variant", action="append", default=[],
                    help="JSON: {\"config\": {...}, \"traffic\": {...}}")
    ap.add_argument("--survey", action="store_true")
    ap.add_argument("--layers", action="store_true")
    args = ap.parse_args(argv)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    import bigdl_tpu  # noqa: F401
    devices = None
    for variant in [json.loads(v) for v in args.variant] or [{}]:
        cell = harness.Cell(args.workload, bench_dir)     # its dicts are this cell's own
        _lay_over(cell.config, variant.get("config", {}))
        _lay_over(cell.traffic, variant.get("traffic", {}))
        if devices is None:
            devices = harness.attach(cell.chips)[0] if require_chip else jax.devices()
        # twice the balanced expectation of a layer's held pairs (`parallel/moe.py`)
        bound = 2 * cell.work.held_pairs(
            cell.config, cell.traffic["batch"] * 2 * cell.traffic["seq_len"])
        count = _counter(cell) if args.survey or args.layers else None
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            line = {"cell": cell.name, "variant": variant, "seed": seed,
                    "row_bound_a_layer": bound}
            line.update(_survey(cell, seed, count) if args.survey else
                        _drive(cell, seed, devices, args.steps, count))
            line["seconds"] = round(time.perf_counter() - t0, 1)
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
