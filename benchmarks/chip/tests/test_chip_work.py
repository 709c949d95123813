"""work.py's counts against hand arithmetic."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import linear_classes  # noqa: E402
import manifest  # noqa: E402
import readers  # noqa: E402
import work  # noqa: E402

QWEN = {"reference": "qwen3", "hidden_size": 2048, "intermediate_size": 6144,
        "num_hidden_layers": 28, "num_attention_heads": 16,
        "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 151936}


def test_linear_counts():
    # 4 rows x [2048 -> 6144] at Pa = Pw = 8, bf16 outputs
    ops, nbytes = work.linear(4, 2048, 6144)
    assert ops == 2 * 4 * 2048 * 6144 == 100_663_296
    assert nbytes == 2048 * 6144 + 4 * 2048 + 4 * 6144 * 2 == 12_640_256
    # Pw = 4 halves the weight bytes only
    assert work.linear(4, 2048, 6144, w_bits=4)[1] == \
        2048 * 6144 // 2 + 4 * 2048 + 4 * 6144 * 2


def test_conv_counts():
    # VGG conv1_1 at 64 px, batch 2: 3x3x3 -> 64
    ops, nbytes = work.conv(2, 64, 64, 3, 64, 3)
    assert ops == 2 * 2 * 64 * 64 * 27 * 64 == 28_311_552
    assert nbytes == 27 * 64 + 2 * 64 * 64 * 3 + 2 * 64 * 64 * 64 * 2 \
        == 1_074_880
    # stride 2 halves each output side
    assert work.conv(1, 8, 8, 4, 4, 3, stride=2)[0] == 2 * 16 * 36 * 4


def test_decode_step_counts():
    # two active rows at contexts 10 and 20
    ops, nbytes = work.decode_step(QWEN, [10, 20])
    per_layer = (2048 * 2048 * 2 + 2048 * 1024 * 2 + 2048 * 6144 * 3)
    lin_params = 28 * per_layer + 2048 * 151936
    assert lin_params == 1_720_451_072
    attn_ops = 28 * 4 * (10 + 20) * 16 * 128
    assert ops == 2 * 2 * lin_params + attn_ops
    lin_bytes = sum(c * (k * n + 2 * k + 2 * n * 2)
                    for _, k, n, c in manifest.load_module(
                        "refs", "qwen3").decode_linears(QWEN))
    kv_bytes = 28 * 2 * (10 + 20) * 8 * 128 * 2
    assert nbytes == lin_bytes + kv_bytes


def _steps_record():
    """Four steps in a 10 s window, one of them empty, and one after it."""
    from types import SimpleNamespace
    steps = [SimpleNamespace(t0=t, contexts=ctx) for t, ctx in (
        (1.0, [600, 700]), (2.0, [601]), (3.0, []),
        (4.0, [1024, 2048, 3000]), (11.0, [5]))]
    return {"kind": "lm", "config": dict(QWEN), "steps": steps,
            "t_w0": 0.0, "t_w1": 10.0}


# Read on the tree where work.py still counted the Qwen3 linears and
# attention itself; the reference module's counts must give them exactly.
PINNED = {
    "decode_step": (6888685568.0, 1727829504.0),
    "decode": 0.006316442725274726,
    "attn": 0.0012947561025641026,
    "mlp": 0.0038796471794871794,
    "lm_head": 0.0011420394432234433,
}


@pytest.mark.parametrize("what", sorted(PINNED))
def test_counts_from_the_reference_module_are_unchanged(what):
    pk = work.peaks("TPU v5 lite")
    rec = _steps_record()
    if what == "decode_step":
        got = work.decode_step(QWEN, [10, 20])
    elif what == "decode":
        got = readers.decode_least_s(rec, pk)
    else:
        got = linear_classes.least_s(rec, pk, linear_classes.CLASSES[what])
    assert got == PINNED[what]


def test_least_time_and_peaks():
    pk = work.peaks("TPU v5 lite")
    assert pk["int8_ops"] == 393e12 and pk["hbm_bytes_per_s"] == 819e9
    # a decode linear is bound by its weight bytes
    ops, nbytes = work.linear(1, 2048, 151936)
    assert work.least_time(ops, nbytes, pk) == nbytes / 819e9
    # a big GEMM is bound by compute
    ops, nbytes = work.linear(4096, 4096, 4096)
    assert work.least_time(ops, nbytes, pk) == ops / 393e12
    with pytest.raises(KeyError):
        work.peaks("cpu")


def test_vgg_classify_ops():
    import json
    with open(os.path.join(os.path.dirname(work.__file__), "configs",
                           "vgg16-cifar.json")) as f:
        cfg = json.load(f)
    layers = work.cnn_layers(cfg)
    assert len([l for l in layers if l[0] == "conv"]) == 13
    assert [l[1:] for l in layers if l[0] == "fc"] == [(512, 10)]
    macs = sum(l[1] * l[2] * l[5] ** 2 * l[3] * l[4] for l in layers
               if l[0] == "conv") + sum(l[1] * l[2] for l in layers
                                         if l[0] == "fc")
    assert work.classify(cfg, 1)[0] == 2 * macs
    # blocks of 32, 16, 8, 4 and 2 px: 9 taps a pixel, C_in x C_out each
    assert macs == (32 * 32 * 9 * (3 * 64 + 64 * 64)
                    + 16 * 16 * 9 * (64 * 128 + 128 * 128)
                    + 8 * 8 * 9 * (128 * 256 + 2 * 256 * 256)
                    + 4 * 4 * 9 * (256 * 512 + 2 * 512 * 512)
                    + 2 * 2 * 9 * 3 * 512 * 512 + 512 * 10) == 313_201_664
