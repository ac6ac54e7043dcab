"""The plain float32 reference against the program's own forward
(`repro.models.api.forward`) at a reduced width, and its fp8 control.

Both run in float32 on the CPU, where a float32 matrix product is exact
float32 whatever the precision flag, so they differ only by the order of
float32 sums (flash attention in blocks against one softmax, scan
against a Python loop): 1e-4 of the logits' scale covers that with room,
and an error in the mathematics (a rotary convention, a norm, a mask,
a head grouping) moves logits by far more."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmarks.chip import bench, serving, small  # noqa: E402

ref = bench.load_module("reference/dense_decoder.py")


def _setup(**over):
    import jax.numpy as jnp
    cfg = small.config(torch_dtype="float32", **over)
    pcfg = serving.program(cfg).model_config(cfg)
    params = serving.make_weights(pcfg, 2**33 + 3)
    # weights from the draw are tiny at this width: scale the embedding
    # up so the logits spread over several units
    params["embed"] = params["embed"] * 50
    assert params["stage1"]["attn"]["wq"].dtype == jnp.float32
    return cfg, pcfg, params


@pytest.mark.parametrize("over", [{}, {"num_key_value_heads": 4},
                                  {"sliding_window": 8}],
                         ids=["gqa", "mha", "window"])
def test_reference_matches_program_forward(over):
    import jax.numpy as jnp
    from repro.models import api
    cfg, pcfg, params = _setup(**over)
    toks = np.random.default_rng(0).integers(2, cfg["vocab_size"], 40)
    got, _, _ = api.forward(params, {"tokens": jnp.asarray(toks)[None]},
                            pcfg, mode="train", remat="none")
    got = np.asarray(got[0, :, :cfg["vocab_size"]])
    w = serving.program(cfg).reference_weights(params)
    want = np.asarray(ref.logits(w, cfg, toks))
    scale = np.abs(want).max()
    assert scale > 1.0
    np.testing.assert_allclose(got, want, atol=1e-4 * scale, rtol=0)


def test_served_gaps_zero_for_reference_tokens_and_control_reads_more():
    cfg, _, params = _setup()
    w = serving.program(cfg).reference_weights(params)
    prompt = list(np.random.default_rng(1).integers(2, 256, 20))
    # greedy tokens of the reference itself: every gap is 0
    seq = list(prompt)
    for _ in range(12):
        lg = np.asarray(ref.logits(w, cfg, np.asarray(seq)))
        seq.append(int(lg[-1].argmax()))
    served = seq[len(prompt):]
    gaps = ref.served_gaps(w, cfg, prompt, served, pad_to=64)
    assert gaps.shape == (12,)
    assert float(gaps.max()) == 0.0
    ctl = ref.served_gaps(w, cfg, prompt, served, pad_to=64, control=True)
    assert ctl.shape == (12,) and float(ctl.max()) >= 0.0
    # a token that is not the reference's argmax reads its shortfall
    bad = list(served)
    lg = np.asarray(ref.logits(w, cfg, np.asarray(prompt)))[-1]
    bad[0] = int(lg.argmin())
    g = ref.served_gaps(w, cfg, prompt, bad, pad_to=64)
    assert g[0] == pytest.approx(lg.max() - lg.min(), rel=1e-5)
