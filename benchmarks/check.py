"""The comparison that decides `correct` for a training cell.

The program's first steps, through its own `optimize()` and feed, against the
plain reference following the same batches from the same seeded weights:
each step's loss, the first gradient as the optimizer got it and, after the
last of those steps, the parameters' change and the optimizer's first-moment
buffer, each by the worst leaf and by the median leaf. With `fuse_steps` 8 the
last step is the end of the first fused window, so change and buffer are what
the window's own program made. A gap between norms is measured against the
reference's norm of that leaf or of the median leaf, whichever is larger.
Which of the numbers a cell is judged by, and why, is in its
`limits/<cell>.json`.
Elements whose reference gradient is under a thousandth of the median leaf's
(root mean square) move by round-off alone under Adam, as a key's bias does
under softmax, and are left out of the change: by element and not by leaf,
because the program keeps the query, key and value biases in one leaf.

`fp8` is the control's rounding: the reference computed in the nearest
precision below the configuration's bfloat16. A reference applies its `q` to
every tensor that the configuration holds in its compute type.
"""

import json
import math
import statistics
import sys
import time

import jax
import jax.numpy as jnp


def _round(x, dtype, top):
    scale = jnp.max(jnp.abs(x)) / top + 1e-30
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def identity(x):
    return x


@jax.custom_vjp
def fp8(x):
    """A tensor as an fp8 pipeline holds it: rounded to float8 e4m3 with one
    scale for the tensor, its gradient rounded to float8 e5m2 likewise."""
    return _round(x, jnp.float8_e4m3fn, 448.0)


fp8.defvjp(lambda x: (fp8(x), None),
           lambda _, g: (_round(g, jnp.float8_e5m2, 57344.0),))


@jax.jit
def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


@jax.jit
def moving(grad, threshold):
    """Which elements the change is read over: those whose reference gradient
    is at least `threshold` in size."""
    return {k: jnp.abs(v) >= threshold for k, v in grad.items()}


@jax.jit
def change_norms(after, before, mask):
    return {k: jnp.sqrt(jnp.sum(jnp.where(mask[k], jnp.square(
        after[k].astype(jnp.float32) - before[k].astype(jnp.float32)), 0.0)))
        for k in before}


def gradient_floor(norms, shapes):
    """A thousandth of the median leaf's root-mean-square gradient."""
    sizes = {k: max(1, math.prod(s)) for k, s in shapes}
    return 1e-3 * statistics.median(norms[k] / sizes[k] ** 0.5 for k in norms)


_compiled = {}


def _loss_and_grad(reference, cfg, q):
    """One compiled program for each reference, configuration and rounding: a
    calibration follows many seeds in one process."""
    key = (reference.__name__, json.dumps(cfg, sort_keys=True), q)
    if key not in _compiled:
        _compiled[key] = reference.make_loss_and_grad(cfg, q)
    return _compiled[key]


def follow(reference, method, cfg, traffic, weights, batches,
           q=identity, rows=None, mask=None, frozen=False):
    """The reference's readings over `batches` (the batches of the program's
    first steps, in its order), from `weights`: each loss, the first
    gradient's norm by leaf and, after the last step, the norms by leaf of
    the parameters' change and of the optimizer's first-moment buffer, and
    `mask`, the elements that change was read over (those the first gradient
    moves; see `moving`). `method` is the cell's `methods/<class>.py`. `q`,
    `rows` and a `mask` given are for the control and the planted faults, read
    in the program's place, as is `frozen`: every step returns its state
    unchanged."""
    step, init = method.make(traffic["optim_method"]["args"])
    loss_and_grad = _loss_and_grad(reference, cfg, q)
    # the steps update their copy in place; `weights` stays for the change
    p, state = jax.tree_util.tree_map(jnp.copy, weights), init(weights)
    out = {"losses": []}
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        for t, (x, y) in enumerate(batches, 1):
            if rows is not None:
                x, y = x[rows], y[rows]
            loss, g = loss_and_grad(p, jnp.asarray(x), jnp.asarray(y))
            out["losses"].append(loss)
            if t == 1:
                out["grad1"] = floats(leaf_norms(g))
                if mask is None:
                    mask = moving(g, gradient_floor(
                        out["grad1"], [(k, v.shape) for k, v in g.items()]))
                out["mask"] = mask
                print(f"reference: step 1 took {time.perf_counter() - t0:.1f} s",
                      file=sys.stderr)
            if not frozen:
                p, state = step(p, g, state, float(t))
        out["change"] = floats(change_norms(p, weights, mask))
        out["momentum"] = floats(leaf_norms(state[method.SLOT]))
    out["losses"] = [float(v) for v in out["losses"]]
    print(f"reference: {len(batches)} steps took {time.perf_counter() - t0:.1f} s",
          file=sys.stderr)
    return out


def floats(tree):
    return {k: float(v) for k, v in jax.device_get(tree).items()}


def _leaf_gaps(got, want, leaves, worst, name):
    """(worst, median) over `leaves` of the gap between two norms, against the
    reference's norm of that leaf or of the median leaf, whichever is larger."""
    floor = statistics.median(want[k] for k in leaves)
    gaps = sorted((abs(got[k] - want[k]) / max(want[k], floor, 1e-30), k)
                  for k in leaves)
    worst[name] = gaps[-1][1]
    return gaps[-1][0], gaps[len(gaps) // 2][0]


def compare(got, want, worst=None):
    """The numbers compared, by name: `got` from the program (or a control or
    fault in its place), `want` from the reference. `worst`, a dict, is told
    which leaf each worst-leaf number came from."""
    worst = {} if worst is None else worst
    out = {f"loss{i + 1}": abs(a - b) / abs(b)
           for i, (a, b) in enumerate(zip(got["losses"], want["losses"]))}
    leaves = list(want["grad1"])
    moved = [k for k in leaves if want["change"][k] > 0.0]
    for name, over in (("grad1", leaves), ("change", moved), ("momentum", leaves)):
        out[name], out[name + "_median"] = _leaf_gaps(
            got[name], want[name], over, worst, name)
    return out


def judge(numbers, limits):
    """name -> (value, limit) for every number that has a limit, and whether
    all hold. A number with no limit is not judged (its readings and the
    reason are in the cell's limits file and in PERF.md)."""
    compared = {k: (numbers[k], limits[k]) for k in limits}
    ok = all(v == v and v <= lim for v, lim in compared.values())
    return ok, compared


def failed_by(compared):
    """The names of the numbers that do not hold."""
    return [k for k, (v, lim) in compared.items() if not (v == v and v <= lim)]
