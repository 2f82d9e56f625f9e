"""What a configuration file chooses, on the CPU: the model family it
names, each family's contract with the generic code, and the walk of
every segment of the program's layer plan by global layer index."""
import copy
import dataclasses
import importlib
import json
import os

import numpy as np
import pytest

import catalog
import check
import traffic_gen
import weights

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2 ** 32 + 11
CONFIGS = ["internlm2-20b", "qwen1.5-110b"]
# what the generic code calls on a family (catalog, weights, flops,
# check and the readers)
CONTRACT = ("REFERENCE", "model_fields", "layer_weights", "top_weights",
            "adapter_dims", "layer_tree", "backbone_flops_per_token",
            "attention_flops")


@pytest.mark.parametrize("config", CONFIGS)
@pytest.mark.parametrize("family", [None, "dense_gpa"],
                         ids=["missing", "unknown"])
def test_a_missing_or_unknown_family_names_the_known_ones(family, config):
    cfg = json.load(open(os.path.join(BENCH, "configs", config + ".json")))
    cfg.pop("family")
    if family:
        cfg["family"] = family
    cell = catalog.Cell("c", 1, cfg, {}, {}, [], [])
    with pytest.raises(ValueError, match="dense_gqa"):
        catalog.model_config(cell)
    assert "dense_gqa" in catalog.families()


@pytest.mark.parametrize("name", catalog.families())
def test_every_family_keeps_the_contract(name):
    fam = catalog.family({"name": "c", "family": name})
    missing = [a for a in CONTRACT if not hasattr(fam, a)]
    assert not missing, missing
    assert callable(importlib.import_module(fam.REFERENCE)
                    .row_loss_and_grad)


class Slots:
    """A packed rank layout: job k's slot starts at ``offs[k]``."""

    def __init__(self, offs, pads):
        self.offs, self.pads = offs, pads

    def slice_of(self, k):
        return self.offs[k], self.pads[k]


def test_job_leaves_walk_every_segment_by_global_layer():
    """One unscanned layer, then a scanned segment of 2 cycles of 2
    layers: layer l's leaves hold the value l, each job's slot its own
    rank of it."""
    d, R = 3, 8
    ranks, lay = [2, 3], Slots([0, 4], [4, 4])

    def leaf(layers):
        A = np.stack([np.full((d, R), l, np.float32) for l in layers])
        B = np.stack([np.full((R, d), l, np.float32) for l in layers])
        return {"q": {"A": A, "B": B}}
    tree = {"segments": [
        {"0": {"q": {k: v[0] for k, v in leaf([0])["q"].items()}}},
        {"0": leaf([1, 3]), "1": leaf([2, 4])}]}
    got = check.job_leaves(tree, lay, ranks)
    for k, r in enumerate(ranks):
        assert sorted(got[k]) == sorted((l, "q", p) for l in range(5)
                                        for p in "AB")
        for l in range(5):
            assert got[k][(l, "q", "A")].shape == (d, r)
            assert got[k][(l, "q", "B")].shape == (r, d)
            assert np.all(got[k][(l, "q", "A")] == l)
            assert np.all(got[k][(l, "q", "B")] == l)


@pytest.mark.parametrize("config", CONFIGS)
def test_program_trees_walk_every_segment_by_global_layer(config):
    """A dense configuration with one leading unscanned layer and a
    scanned segment of two-layer cycles: every layer of the program's
    trees holds the draws the family makes for its global index."""
    from repro.core.ssm import SharedSuperModel
    from repro.models import model as M
    cfg_file = copy.deepcopy(json.load(open(os.path.join(
        BENCH, "configs", config + ".json"))))
    cfg_file["model"].update(hidden_size=32, intermediate_size=64,
                             num_attention_heads=4, num_key_value_heads=2,
                             head_dim=8, num_hidden_layers=5, vocab_size=64)
    traffic = {"jobs": [{"rank": 2, "rows": 1}, {"rank": 8, "rows": 1}],
               "seq_len": 16, "alpha": 16.0}
    cell = catalog.Cell("tiny", 1, cfg_file, traffic, {}, [], [])
    base = catalog.model_config(cell)
    cfg = dataclasses.replace(base, first_k_dense=1,
                              layer_pattern=base.layer_pattern * 2)
    plan = M.segment_plan(cfg)
    assert [(len(s.specs), s.repeats, s.scanned) for s in plan] == [
        (1, 1, False), (2, 2, True)]
    fam, m = cell.family, cell.model
    specs = traffic_gen.job_specs(traffic, cfg.name)
    ranks = [s.rank for s in specs]
    layout = SharedSuperModel(cfg, specs).layout
    params, adapters = weights.program_trees(fam, cfg, m, ranks, layout,
                                             SEED)
    key = weights.root_key(SEED)
    at = {0: (0, "0", None), 1: (1, "0", 0), 2: (1, "1", 0),
          3: (1, "0", 1), 4: (1, "1", 1)}
    ads = check.job_leaves(adapters, layout, ranks)
    for l, (i, j, c) in at.items():
        seg = params["segments"][i][j]
        pick = (lambda x: x) if c is None else (lambda x: x[c])
        flat = {**seg["attn"], **seg["ffn"], "ln1": seg["ln1"],
                "ln2": seg["ln2"]}
        for n, w in fam.layer_weights(key, m, l).items():
            np.testing.assert_array_equal(pick(flat[n]), w)
        for k, r in enumerate(ranks):
            for t, ab in weights.job_adapter(fam.adapter_dims(m), key, k,
                                             l, r).items():
                for part in ("A", "B"):
                    np.testing.assert_array_equal(ads[k][(l, t, part)],
                                                  ab[part])
