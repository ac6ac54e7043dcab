"""The serving harness's family adapters (`program/<family>.py`): the
dense adapter gives the program's ModelConfig and the reference's weight
names exactly as the harness built them when it knew only dense
decoders; a family without an adapter fails at set-up, before any
weights are drawn; and a run takes its model and its reference weights
from the adapter."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import pytest  # noqa: E402

from benchmarks.chip import bench, serving, small  # noqa: E402

dense = bench.load_module("program/dense_decoder.py")


def _cfg(name):
    return bench.load_json(os.path.join(bench.HERE, "configs",
                                        name + ".json"))


@pytest.mark.parametrize("name,want", [
    ("phi3-mini-3.8b", dict(
        name="phi3-mini-3.8b", family="dense", num_layers=32, d_model=3072,
        num_heads=32, num_kv_heads=32, d_ff=8192, vocab_size=32064,
        head_dim=96, sliding_window=2047, rope_theta=10000.0,
        norm_eps=1e-05, tie_embeddings=False, dtype="bfloat16")),
    ("h2o-danube-1.8b", dict(
        name="h2o-danube-1.8b", family="dense", num_layers=24,
        d_model=2560, num_heads=32, num_kv_heads=8, d_ff=6912,
        vocab_size=32000, head_dim=80, sliding_window=4096,
        rope_theta=10000.0, norm_eps=1e-05, tie_embeddings=False,
        dtype="bfloat16"))])
def test_dense_model_config_is_pinned(name, want):
    from repro.configs.base import ModelConfig
    got = dense.model_config(_cfg(name))
    assert got == ModelConfig(**want)
    assert serving.program(_cfg(name)).model_config(_cfg(name)) == got


def test_dense_reference_weights_are_the_same_arrays():
    leaf = {k: object() for k in (
        "embed", "lm_head", "final_norm", "norm1", "norm2", "wq", "wk", "wv",
        "wo", "w_gate", "w_up", "w_down")}
    params = {"embed": leaf["embed"], "lm_head": leaf["lm_head"],
              "final_norm": leaf["final_norm"],
              "stage1": {"norm1": leaf["norm1"], "norm2": leaf["norm2"],
                         "attn": {k: leaf[k] for k in
                                  ("wq", "wk", "wv", "wo")},
                         "mlp": {k: leaf[k] for k in
                                 ("w_gate", "w_up", "w_down")}}}
    w = dense.reference_weights(params)
    want = {"embed": "embed", "lm_head": "lm_head",
            "final_norm": "final_norm", "attn_norm": "norm1",
            "mlp_norm": "norm2", "wq": "wq", "wk": "wk", "wv": "wv",
            "wo": "wo", "w_gate": "w_gate", "w_up": "w_up",
            "w_down": "w_down"}
    assert list(w) == list(want)
    for name, src in want.items():
        assert w[name] is leaf[src], name


def test_family_without_adapter_fails_before_weights(monkeypatch):
    drawn = []
    monkeypatch.setattr(serving, "make_weights",
                        lambda *a: drawn.append(a))
    cfg = small.config(family="no_such_family")
    with pytest.raises(FileNotFoundError, match="program/no_such_family.py"):
        serving.prepare(cfg, small.CLOSED, 1, 1.0, 0.0)
    assert drawn == []


def test_run_takes_model_and_reference_weights_from_the_adapter(
        monkeypatch):
    calls = {"model": [], "ref": [], "built": []}

    class Recording:
        @staticmethod
        def model_config(cfg):
            pcfg = dense.model_config(cfg)
            calls["model"].append(pcfg)
            return pcfg

        @staticmethod
        def reference_weights(params):
            calls["ref"].append(params)
            return dense.reference_weights(params)

    load = bench.load_module

    def patched(relpath):
        if relpath == "program/dense_decoder.py":
            return Recording
        return load(relpath)
    monkeypatch.setattr(bench, "load_module", patched)
    build = serving.build_engine

    def recording_build(cfg, pcfg, params, settings):
        calls["built"].append((pcfg, params))
        return build(cfg, pcfg, params, settings)
    monkeypatch.setattr(serving, "build_engine", recording_build)
    res = small.run(small.CLOSED)
    assert res["correct"], res["compared"]
    (pcfg, params), = calls["built"]
    assert calls["model"] == [pcfg] and calls["model"][0] is pcfg
    assert len(calls["ref"]) == 1 and calls["ref"][0] is params
