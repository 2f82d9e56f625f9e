"""The weights and the reference on the CPU at a tiny size: the program's
stacked trees hold, layer by layer, the very draws the reference makes
by name; the reference's layer-by-layer gradient is the whole model's;
and cutting a row's trailing padding changes neither loss nor gradient."""
import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import catalog
import check
import traffic_gen
import weights
from families import dense_gqa
from reference import model as ref

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SHRINK = {"hidden_size": 32, "intermediate_size": 64,
          "num_attention_heads": 4, "num_key_value_heads": 2,
          "head_dim": 8, "num_hidden_layers": 3, "vocab_size": 64}
TRAFFIC = {"jobs": [{"rank": 2, "rows": 1}, {"rank": 8, "rows": 2}],
           "seq_len": 16, "alpha": 16.0, "chips": 1,
           "lengths": {"law": "lognormal", "mu": 2.0, "sigma": 0.4,
                       "min": 2, "max": 16}}
SEED = 2 ** 33 + 5


def tiny_cell(config: str) -> catalog.Cell:
    cfg = copy.deepcopy(json.load(open(os.path.join(
        BENCH, "configs", config + ".json"))))
    cfg["model"].update(SHRINK)
    return catalog.Cell("tiny", 1, cfg, TRAFFIC, {}, [], [])


@pytest.mark.parametrize("config", ["internlm2-20b", "qwen1.5-110b"])
def test_program_trees_hold_the_reference_draws(config):
    from repro.core.ssm import SharedSuperModel
    cell = tiny_cell(config)
    m = cell.model
    cfg = catalog.model_config(cell)
    specs = traffic_gen.job_specs(TRAFFIC, cfg.name)
    ranks = [s.rank for s in specs]
    layout = SharedSuperModel(cfg, specs).layout
    fam = cell.family
    params, adapters = weights.program_trees(fam, cfg, m, ranks, layout,
                                             SEED)
    key = weights.root_key(SEED)
    seg = params["segments"][0]["0"]
    flat = {**seg["attn"], **seg["ffn"], "ln1": seg["ln1"], "ln2": seg["ln2"]}
    assert ("bq" in flat) == m["qkv_bias"]
    top = fam.top_weights(key, m)
    for n in ("embed", "head", "ln_f"):
        np.testing.assert_array_equal(params[n], top[n])
    ads = check.job_leaves(adapters, layout, ranks)
    for l in range(m["num_hidden_layers"]):
        want = fam.layer_weights(key, m, l)
        assert set(want) == set(flat)
        for n, w in want.items():
            np.testing.assert_array_equal(flat[n][l], w)
        for k, r in enumerate(ranks):
            for t, ab in weights.job_adapter(fam.adapter_dims(m), key, k,
                                             l, r).items():
                for part in ("A", "B"):
                    np.testing.assert_array_equal(ads[k][(l, t, part)],
                                                  ab[part])


def close(a, b):
    """Equal to float32 rounding, measured against the leaf's scale."""
    np.testing.assert_allclose(a, b, rtol=0,
                               atol=1e-5 * float(np.max(np.abs(b))))


def tiny_model(key, m, rank):
    L = m["num_hidden_layers"]
    top = dense_gqa.top_weights(key, m)
    layers = [dense_gqa.layer_weights(key, m, l) for l in range(L)]
    ads = [weights.job_adapter(dense_gqa.adapter_dims(m), key, 0, l, rank)
           for l in range(L)]
    return top, layers, ads


@pytest.mark.parametrize("fp8", [False, True], ids=["f32", "fp8"])
def test_layer_by_layer_gradient_is_the_whole_models(fp8):
    m = tiny_cell("qwen1.5-110b").model
    key = weights.root_key(SEED)
    top, layers, ads = tiny_model(key, m, 4)
    tokens = jax.random.randint(key, (16,), 3, m["vocab_size"])
    mask = (jnp.arange(16) < 11).astype(jnp.float32)
    scale = jnp.float32(4.0)

    def whole(a):
        with jax.default_matmul_precision("highest"):
            x = top["embed"][tokens].astype(jnp.float32)
            for w, ad in zip(layers, a):
                x = ref.block(m, w, ad, x, scale, fp8)
            return ref.head_loss(m, top["ln_f"], top["head"], x, tokens,
                                 mask, fp8)
    (want_l, want_c), want_g = jax.value_and_grad(whole, has_aux=True)(ads)
    loss, count, grads = ref.row_loss_and_grad(m, fp8)(
        top, layers, ads, tokens, mask, scale)
    np.testing.assert_allclose(loss, want_l, rtol=1e-6)
    assert float(count) == float(want_c) == 10.0
    jax.tree.map(close, grads, want_g)


def test_trimmed_row_gives_the_same_loss_and_gradient():
    m = tiny_cell("internlm2-20b").model
    key = weights.root_key(SEED)
    top, layers, ads = tiny_model(key, m, 8)
    tokens = np.asarray(jax.random.randint(key, (32,), 3, m["vocab_size"]))
    mask = (np.arange(32) < 13).astype(np.float32)
    tokens = np.where(mask > 0, tokens, 0)
    n = check.trimmed(mask, bucket=8)
    assert n == 16
    fn = ref.row_loss_and_grad(m)
    scale = jnp.float32(2.0)
    full = fn(top, layers, ads, tokens, mask, scale)
    cut = fn(top, layers, ads, tokens[:n], mask[:n], scale)
    np.testing.assert_allclose(cut[0], full[0], rtol=1e-6)
    assert float(cut[1]) == float(full[1]) == 12.0
    jax.tree.map(close, cut[2], full[2])
