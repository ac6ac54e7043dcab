"""Work functions counted by hand on a small dense decoder, and the peaks
table: a known device's published peaks, an unknown device an error."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import pytest  # noqa: E402

from benchmarks.chip import bench  # noqa: E402

work = bench.load_module("work/dense_decoder.py")

# L=2, d=8, 2 heads and 1 KV head of 4, MLP 16, vocabulary 10, bf16
SMALL = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 2,
         "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 16,
         "vocab_size": 10, "torch_dtype": "bfloat16"}


def test_weights_by_hand():
    # per layer: q 8*8 + k,v 2*8*4 + o 8*8 + MLP 3*8*16 = 576 weights
    assert work.layer_matmul_params(SMALL) == 576
    # 2 bytes * (2 layers * (576 + 2 norms of 8) + final norm 8 + head 80)
    assert work.weight_bytes(SMALL) == 2 * (2 * 592 + 8 + 80) == 2544
    # keys and values: 2 bytes * 2 layers * 2 * 1 head * 4
    assert work.kv_bytes_per_position(SMALL) == 32


def test_token_and_decode_step_by_hand():
    # 2 * (2 * 576 + 8 * 10) = 2464, plus attention 2 layers * 4 * 2 * 4
    # per position
    assert work.token_flops(SMALL, 5) == 2464 + 64 * 5 == 2784
    flops, nbytes = work.decode_step(SMALL, [5, 3])
    assert flops == 2784 + 2464 + 64 * 3 == 5440
    # weights + 2 embedding rows of 8 + the cache read (4 + 2 positions)
    # + the two new positions written
    assert nbytes == 2544 + 2 * 8 * 2 + 32 * 6 + 32 * 2 == 2832


def test_window_bounds_the_span():
    m = dict(SMALL, sliding_window=4)
    assert work.token_flops(m, 5) == work.token_flops(SMALL, 4)
    flops, nbytes = work.decode_step(m, [5, 3])
    assert (flops, nbytes) == (2720 + 2656, 2544 + 32 + 32 * 5 + 64)


def test_least_seconds_takes_the_binding_bound():
    peaks = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e3}
    assert work.least_seconds(5440, 2832, peaks) == pytest.approx(5.44)
    peaks = {"bf16_flops_per_s": 1e4, "hbm_bytes_per_s": 1e3}
    assert work.least_seconds(5440, 2832, peaks) == pytest.approx(2.832)


def test_peaks_known_and_unknown_device():
    p = bench.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        bench.peaks_for("cpu")


def test_phi3_decode_needs_its_weights():
    """At phi3-mini width a decode step is bound by bytes: 7.4 GB of
    weights at 819 GB/s is 9.1 ms, and a batch of 8 at 600 positions
    adds its 1.9 GB of cache."""
    cfg = bench.load_json(os.path.join(bench.HERE, "configs",
                                       "phi3-mini-3.8b.json"))
    # 7.445 GB: the layers and the LM head (the embedding table is read
    # only by its rows)
    assert work.weight_bytes(cfg) == pytest.approx(7.445e9, rel=0.001)
    t = work.least_seconds(*work.decode_step(cfg, [600] * 8),
                           bench.peaks_for("TPU v5 lite"))
    assert 9e-3 < t < 12e-3
