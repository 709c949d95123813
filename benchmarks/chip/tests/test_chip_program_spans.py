"""program_spans.py, linear_classes.py and the six metric readers that
read the program's own spans and kernel names, on hand-made traces."""
import json
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import linear_classes  # noqa: E402
import manifest  # noqa: E402
import program_spans  # noqa: E402
import readers  # noqa: E402
import trace_reduce  # noqa: E402
import work  # noqa: E402

US = 1_000_000  # picoseconds in a microsecond
NEW_METRICS = ("decode_dispatch_ms", "emit_ms", "admission_ms",
               "bitserial_matmul_roofline.attn",
               "bitserial_matmul_roofline.mlp",
               "bitserial_matmul_roofline.lm_head")


def _plane(pid, name, lines):
    """A plane from ``{line: [(event, start_us, dur_us, {stat: int})]}``."""
    names = sorted({e[0] for evs in lines.values() for e in evs})
    stats = sorted({k for evs in lines.values() for e in evs for k in e[3]})
    ev_id = {n: i + 1 for i, n in enumerate(names)}
    st_id = {n: i + 1 for i, n in enumerate(stats)}
    out = f'planes {{ id: {pid} name: "{name}"\n'
    for lid, (line, evs) in enumerate(lines.items(), 1):
        out += f'lines {{ id: {lid} name: "{line}" timestamp_ns: 0\n'
        for ev, a, d, st in evs:
            s = "".join(f"stats {{ metadata_id: {st_id[k]} int64_value: {v} }} "
                        for k, v in st.items())
            out += (f"events {{ metadata_id: {ev_id[ev]} offset_ps: {a * US} "
                    f"duration_ps: {d * US} {s}}}\n")
        out += "}\n"
    out += "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}\n' for n, i in ev_id.items())
    out += "".join(f'stat_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}\n' for n, i in st_id.items())
    return out + "}\n"


def _kernels(named: bool):
    """Decode kernels 10-30 us: q 2, o 1, up 4, down 3, lm_head 10 us."""
    classes = [("attn_q", 10, 2), ("attn_o", 12, 1), ("ffn_up", 13, 4),
               ("ffn_down", 17, 3), ("lm_head", 20, 10)]
    return [(f"%bitserial_matmul{'_' + c if named else ''}.{i} = "
             f"s32[8,128]{{1,0}} custom-call()", a, d, {})
            for i, (c, a, d) in enumerate(classes, 3)]


def write_trace(tmp_path, program: bool):
    """Window 0-100 us. Device: a decode program 10-40 us holding the
    five kernels and a fusion 30-35 us; a prefill program 50-60 us with
    an attn_q kernel 50-55 us (not decode). Host, with ``program``: two
    engine steps with their phase spans, an admission in the first, and
    a decode span after the window."""
    pfx = "%bitserial_matmul_attn_q" if program else "%bitserial_matmul"
    dev = _plane(1, "/device:TPU:0", {
        "XLA Modules": [("jit_decode_fn(1)", 10, 30, {}),
                        ("jit_prefill_fn(2)", 50, 10, {})],
        "XLA Ops": _kernels(program) + [
            ("%fusion.8 = f32[8]{0} fusion()", 30, 5, {}),
            (f"{pfx}.9 = s32[512,128]{{1,0}} custom-call()", 50, 5, {})]})
    host = [("bench.window", 0, 100, {}), ("bench.engine_step", 0, 45, {}),
            ("bench.engine_step", 48, 22, {})]
    if program:
        host += [
            ("loom.engine.step", 0, 45, {"step_num": 0, "n_active": 0}),
            ("loom.engine.admit", 1, 8, {"n_admitted": 1}),
            ("loom.engine.prefill", 2, 5,
             {"request_id": 5, "slot": 0, "prompt_len": 512}),
            ("loom.engine.scatter", 7, 1, {"request_id": 5, "slot": 0}),
            ("loom.engine.decode", 9, 1, {"n_active": 1}),
            ("loom.engine.sync", 10, 30, {}),
            ("loom.engine.emit", 40, 4, {}),
            ("loom.engine.step", 48, 22, {"step_num": 1, "n_active": 1}),
            ("loom.engine.admit", 48, 1, {"n_admitted": 0}),
            ("loom.engine.decode", 49, 2, {"n_active": 1}),
            ("loom.engine.sync", 51, 14, {}),
            ("loom.engine.emit", 65, 3, {}),
            ("loom.engine.decode", 100, 2, {"n_active": 1})]
    from jax.profiler import ProfileData
    txt = dev + _plane(2, "/host:CPU", {"main": host})
    d = tmp_path / program_spans.TRACE_DIR.rsplit(os.sep, 1)[-1]
    d.mkdir()
    (d / "t.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(txt))
    return str(d)


def _ctx(tmp_path, monkeypatch, program: bool):
    d = write_trace(tmp_path, program)
    monkeypatch.setattr(program_spans, "TRACE_DIR", d)
    with open(os.path.join(HERE, "configs", "qwen3-1.7b.json")) as f:
        cfg = json.load(f)
    steps = [SimpleNamespace(t0=1.0, contexts=[600, 700]),
             SimpleNamespace(t0=2.0, contexts=[601]),
             SimpleNamespace(t0=3.0, contexts=[])]
    rec = {"kind": "lm", "config": cfg, "steps": steps, "t_w0": 0.0,
           "t_w1": 10.0}
    return {"trace": trace_reduce.reduce_dir(d), "rec": rec,
            "peaks": work.peaks("TPU v5 lite")}


def _read(ctx, name):
    return manifest.load_module("metrics", name).read(ctx)


def test_spans_in_the_window_with_their_stats(tmp_path, monkeypatch):
    ctx = _ctx(tmp_path, monkeypatch, program=True)
    spans = program_spans.in_window(ctx)
    assert len(spans) == 12                 # the decode at 100 us is out
    assert [s.name for s in spans[:4]] == [
        "loom.engine.step", "loom.engine.admit", "loom.engine.prefill",
        "loom.engine.scatter"]
    pre = program_spans.in_window(ctx, "loom.engine.prefill")
    assert [s.stats for s in pre] == [
        {"request_id": 5, "slot": 0, "prompt_len": 512}]
    assert pre[0].dur == 5000
    assert program_spans.in_window(dict(ctx, trace=None)) == []
    monkeypatch.setattr(program_spans, "TRACE_DIR", str(tmp_path / "none"))
    assert program_spans.in_window(ctx) == []


def test_engine_readers(tmp_path, monkeypatch):
    ctx = _ctx(tmp_path, monkeypatch, program=True)
    assert _read(ctx, "decode_dispatch_ms") == pytest.approx(1.5e-3)
    assert _read(ctx, "emit_ms") == pytest.approx(3.5e-3)
    # one admission: prefill 5 us + scatter 1 us
    assert _read(ctx, "admission_ms") == pytest.approx(6e-3)


def test_class_rooflines_split_the_decode_roofline(tmp_path, monkeypatch):
    ctx = _ctx(tmp_path, monkeypatch, program=True)
    pk, rec = ctx["peaks"], ctx["rec"]

    def least(names):
        return sum(count * work.least_time(*work.linear(rows, k, n), pk)
                   for rows in (2, 1)
                   for name, k, n, count in manifest.load_module(
                       "refs", rec["config"]["reference"]).decode_linears(
                           rec["config"])
                   if name in names)

    want = {"attn": (least({"attn_q", "attn_k", "attn_v", "attn_o"}), 3e-6),
            "mlp": (least({"ffn_gate", "ffn_up", "ffn_down"}), 7e-6),
            "lm_head": (least({"lm_head"}), 10e-6)}
    for cls, (least_s, kernel_s) in want.items():
        assert _read(ctx, f"bitserial_matmul_roofline.{cls}") == \
            pytest.approx(100 * least_s / kernel_s)
    # the classes hold every decode kernel and all of the decode least time
    tr = ctx["trace"]
    total = sum(op.dur for op in tr.ops_in(tr.programs("decode"),
                                           r"^bitserial_matmul")) / 1e9
    assert sum(linear_classes.kernel_s(tr, p)
               for p in linear_classes.CLASSES.values()) == \
        pytest.approx(total)
    assert sum(v[0] for v in want.values()) == \
        pytest.approx(readers.decode_least_s(rec, pk))


def test_a_program_without_spans_or_names_reads_nothing(tmp_path,
                                                        monkeypatch):
    """The parent of this change: unnamed kernels, no loom spans. The six
    readers give None; the whole-decode roofline still reads."""
    ctx = _ctx(tmp_path, monkeypatch, program=False)
    for name in NEW_METRICS:
        assert _read(ctx, name) is None, name
    assert _read(ctx, "bitserial_matmul_roofline.decode") is not None
    for name in NEW_METRICS:
        assert _read(dict(ctx, trace=None), name) is None, name
