"""trace_reduce.py and the metric readers on a hand-made trace and on one
recorded on the chip."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import manifest  # noqa: E402
import trace_reduce  # noqa: E402

US = 1_000_000  # picoseconds in a microsecond


def _line(lid, name, events):
    ev = "".join(f"events {{ metadata_id: {m} offset_ps: {a * US} "
                 f"duration_ps: {d * US} }}\n" for m, a, d in events)
    return f'lines {{ id: {lid} name: "{name}" timestamp_ns: 0\n{ev}}}\n'


def _meta(names):
    return "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}\n' for i, n in names.items())


def synthetic():
    """Window 0-20 us. Device: decode program 1-9 us holding a matmul
    kernel 2-5 us and a fusion 4-6 us; prefill program 12-16 us with
    one op 12-15 us. Host: engine step 0-10 us, sleep 10-11 us, engine
    step 11-17 us; an op before the window is left out."""
    dev = _meta({1: "jit_decode_fn(7)", 2: "jit_prefill_fn(8)",
                 3: "%bitserial_matmul.3 = s32[32,6144]{1,0} custom-call()",
                 4: "%fusion.2 = f32[32,6144]{1,0} fusion()",
                 5: "%bitserial_matmul.9 = s32[512,2048]{1,0} custom-call()"})
    host = _meta({1: "bench.window", 2: "bench.engine_step",
                  3: "bench.sleep", 4: "unrelated"})
    txt = ('planes { id: 1 name: "/device:TPU:0"\n'
           + _line(1, "XLA Modules", [(1, 1, 8), (2, 12, 4)])
           + _line(2, "XLA Ops", [(3, 2, 3), (4, 4, 2), (5, 12, 3)])
           + _line(3, "Async XLA Ops", [(4, 0, 20)])
           + dev + "}\n"
           + 'planes { id: 2 name: "/host:CPU"\n'
           + _line(1, "main", [(1, 0, 20), (2, 0, 10), (3, 10, 1),
                               (2, 11, 6), (4, 0, 1)])
           + host + "}\n")
    from jax.profiler import ProfileData
    return trace_reduce.reduce_profile(ProfileData.from_text_proto(txt))


def test_busy_idle_and_programs_by_hand():
    red = synthetic()
    assert red.window_s == 20e-6
    # ops 2-6 and 12-15 us: 7 us busy (the async line is not busy)
    assert red.busy_s == pytest.approx(7e-6)
    assert [r.name for r in red.programs("decode")] == ["jit_decode_fn(7)"]
    runs = red.programs("decode")
    assert [trace_reduce.op_name(o.name)
            for o in red.ops_in(runs, r"^bitserial_matmul")] == [
        "bitserial_matmul.3"]
    # idle: 0-2 and 6-10 us in a step, 10-11 in a sleep, 11-12 and
    # 15-20 in or after the second step (17-20 outside any span)
    idle = red.idle_by_span()
    assert idle["bench.engine_step"] == pytest.approx(9e-6)
    assert idle["bench.sleep"] == pytest.approx(1e-6)
    assert idle["outside any bench span"] == pytest.approx(3e-6)
    bd = red.breakdown()
    assert bd["device_ops"][0] == ["jit_decode_fn/bitserial_matmul.3",
                                   pytest.approx(3e-6)]
    assert len(bd["device_ops"]) == 3


def test_breakdown_counts_self_time():
    """A loop op that holds two kernels is charged only its own time."""
    ev = trace_reduce.Event
    ops = [ev("%while.3 = while()", 0, 100), ev("%bitserial_matmul.1 = x", 10, 40),
           ev("%fusion.2 = y", 50, 60), ev("%copy.4 = z", 100, 120)]
    own = {trace_reduce.op_name(e.name): t
           for e, t in trace_reduce._self_times(ops)}
    assert own == {"while.3": 60, "bitserial_matmul.1": 30, "fusion.2": 10,
                   "copy.4": 20}


def test_metric_readers_on_the_hand_made_trace():
    red = synthetic()
    ctx = {"trace": red, "rec": {"kind": "lm"}, "peaks": None}
    read = lambda n: manifest.load_module("metrics", n).read(ctx)
    assert read("decode_step_ms") == pytest.approx(8e-3)
    # steps 0-10 (4 us busy) and 11-17 (3 us busy): (6 + 3) / 2 us
    assert read("step_host_ms") == pytest.approx(4.5e-3)
    assert read("idle_pct.lm") == pytest.approx(65.0)
    assert read("idle_pct.cnn") is None


RECORDED = os.path.join(HERE, "testdata", "vgg16_64px_b256.xplane.pb")


def test_recorded_chip_trace():
    """0.2 s of VGG-16 at 64 px (head 4096-4096-1000), batch 256, traced
    on a TPU v5e: four classify calls of 13 conv and 3 FC kernels each."""
    import json

    import work
    red = trace_reduce.reduce_file(RECORDED)
    assert red.window_s == pytest.approx(0.203408808)
    assert red.busy_s == pytest.approx(0.196176468)
    spans = red.host_spans("bench.classify")
    assert len(spans) == 4
    names = [trace_reduce.op_name(o.name) for o in red.ops_in(spans, "")]
    assert sum(n.startswith("bitserial_conv") for n in names) == 4 * 13
    assert sum(n.startswith("bitserial_matmul") for n in names) == 4 * 3
    with open(os.path.join(HERE, "configs", "vgg16-cifar.json")) as f:
        cfg = dict(json.load(f), image_size=64, fcs=[4096, 4096, 1000])
    ctx = {"trace": red, "rec": {"kind": "cnn", "batch": 256, "config": cfg},
           "peaks": work.peaks("TPU v5 lite")}
    read = lambda n: manifest.load_module("metrics", n).read(ctx)
    # the window's busy time over its four calls
    assert read("classify_ms") == pytest.approx(0.196176468 / 4 * 1e3)
    assert read("idle_pct.cnn") == pytest.approx(3.5555687)
    assert read("bitserial_conv_roofline.cnn") == pytest.approx(4.7575560)
    assert read("classify_mfu_pct") == pytest.approx(3.2845202)
    assert read("decode_step_ms") is None
    bd = red.breakdown()
    assert bd["device_ops"][0][0] == "jit__lambda/bitserial_conv.21"
    assert [k for k, _ in bd["idle_gaps"]] == ["bench.classify",
                                               "outside any bench span"]
