"""The control of each configuration comes out as not correct: the
reference, computed at the lower precision the configuration's
``control`` names, put in the program's place and judged by the same
checks as the program."""
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import checks  # noqa: E402
import manifest  # noqa: E402
import rehearsal  # noqa: E402


def _config(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_lm_control_is_not_correct():
    """qwen3-smoke served by the program on the CPU: the program's run is
    correct, and the int4-weight reference's first choice at each served
    position, in the program's place, is not."""
    c = _config("qwen3-1.7b")
    ctl = (c["control"]["a_bits"], c["control"]["w_bits"])
    driver = manifest.load_module("drivers", "lm")
    rec = driver.run(rehearsal.lm_cell(), 2**31 + 3, 1.5, None,
                     t_start=time.perf_counter(), log=lambda m: None,
                     control=ctl)
    assert rec["correct"], rec["checks"]
    assert not checks.correct(rec["control_checks"]), rec["control_checks"]


def test_cnn_control_is_not_correct():
    """VGG-16 on 32 px, 64 images, on the CPU: the int4-weight
    reference's logits, in the program's place, fail the check that the
    float32 reference sets."""
    import jax
    c = _config("vgg16-cifar")
    ref = manifest.load_module("refs", "vgg")
    w = ref.make_weights(2**31 + 4, c)
    x = ref.make_images(2**31 + 4, c, 1, 64)[0]
    walk = ref.layers(c)
    want = ref.logits(w, x, walk)
    got = ref.logits(w, x, walk, c["control"]["a_bits"],
                     c["control"]["w_bits"])
    jax.block_until_ready((want, got))
    driver = manifest.load_module("drivers", "cnn")
    assert checks.correct(driver.checks_of(c, 0.0))
    assert not checks.correct(driver.checks_of(
        c, ref.logit_error(got, want)))


def test_a_missing_limit_or_nan_fails():
    assert checks.correct({"a": (0.5, 1.0), "b": (0.0, 0.0)})
    assert not checks.correct({"a": (0.5, None)})
    assert not checks.correct({"a": (float("nan"), 1.0)})
    assert not checks.correct({"a": (0.5, 1.0), "b": (1.0, 0.0)})
