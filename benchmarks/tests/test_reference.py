"""The two plain references against the program at a tiny size, both in
float32: the same weights and batch give the same loss and gradient."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness

CUT = {"gpt2-medium": ({"n_embd": 64, "n_head": 4, "n_layer": 2, "vocab_size": 512,
                        "n_positions": 32}, {"batch": 4, "seq_len": 32, "n_batches": 1}),
       "resnet50": ({"image_size": 64, "class_num": 10},
                    {"batch": 8, "n_batches": 1, "label_classes": 4})}


# ResNet-50 at 8 images is chaotic (a 1e-7 change of the weights moves every
# leaf's gradient by 1%), so only its loss is held tightly; see PERF.md
@pytest.mark.parametrize("name,loss_tol,grad_tol", [("gpt2-medium", 1e-5, 1e-3),
                                                    ("resnet50", 1e-4, 0.25)])
def test_reference_matches_program(name, loss_tol, grad_tol):
    from bigdl_tpu import Engine
    Engine.init(seed=1)
    Engine.set_compute_dtype(jnp.float32)
    cfg = json.load(open(os.path.join(harness.HERE, "configs", name + ".json")))
    cfg.update(CUT[name][0])
    traffic = CUT[name][1]
    mod = harness.load_module(os.path.join(harness.HERE, "configs", name + ".py"))
    ref = harness.load_module(os.path.join(harness.HERE, "reference", name + ".py"))
    model, criterion = mod.build(cfg, traffic)
    names = mod.names(cfg)
    weights = mod.make_weights(cfg, harness.seed_key(2 ** 31 + 5))
    params = harness.tree_from_names(model.get_params(), names, weights)
    x, y = mod.make_batches(cfg, traffic, np.random.default_rng(0))[0]
    model.training()

    def program_loss(p):
        out, _ = model.apply(p, model.get_state(), jnp.asarray(x), training=True,
                             rng=None)
        return criterion.apply(out, jnp.asarray(y))

    lp, gp = jax.value_and_grad(program_loss)(params)
    lr, gr = ref.make_loss_and_grad(cfg)(weights, jnp.asarray(x), jnp.asarray(y))
    assert abs(float(lp) - float(lr)) <= loss_tol * abs(float(lr))
    gp = harness.names_from_tree(gp, names)
    for k in gr:
        scale = float(jnp.linalg.norm(gr[k])) + 1e-12
        assert float(jnp.linalg.norm(gp[k] - gr[k])) <= grad_tol * scale + 1e-7, k
