"""Regenerate the generated sections of EXPERIMENTS.md from results JSONs:
the §Roofline table (between ROOFLINE_TABLE markers) and the §Perf chain
tables (PERF_CHAIN:<arch>:<shape> markers). Narrative text is hand-written;
numbers are spliced from results/ so the document can never go stale.

    PYTHONPATH=src python -m benchmarks.gen_experiments
"""
import glob
import json
import os
import re

ROOT = os.path.join(os.path.dirname(__file__), "..")


def load(d):
    out = []
    for p in sorted(glob.glob(os.path.join(ROOT, d, "*.json"))):
        with open(p) as f:
            out.append(json.load(f))
    return out


def roofline_table():
    from benchmarks import roofline
    recs = [r for r in load("results/dryrun") if not r.get("skipped")
            and not r.get("tag")]
    return roofline.table(recs, mesh="single")


def chain_table(arch, shape, steps):
    """steps: list of (label, tag, weights). Pull each from results dirs."""
    perf = {(r["arch"], r["shape"], r.get("tag", ""), r["weights"]): r
            for r in load("results/perf") if not r.get("skipped")}
    base = {(r["arch"], r["shape"], r.get("tag", ""), r["weights"]): r
            for r in load("results/dryrun") if not r.get("skipped")
            and r["mesh"] == "single"}
    rows = ["| step | compute ms | memory ms | collective ms | bound ms | "
            "roofline frac | Δbound |", "|---|---|---|---|---|---|---|"]
    prev = None
    for label, tag, weights in steps:
        r = perf.get((arch, shape, tag, weights)) \
            or base.get((arch, shape, tag, weights))
        if r is None:
            rows.append(f"| {label} | (missing) | | | | | |")
            continue
        bound = r["bound_s"]
        delta = "" if prev is None else f"{prev / bound:.2f}x"
        rows.append(
            f"| {label} | {r['t_compute_s']*1e3:.1f} "
            f"| {r['t_memory_s']*1e3:.1f} | {r['t_collective_s']*1e3:.1f} "
            f"| {bound*1e3:.1f} | {r['roofline_fraction']:.3f} | {delta} |")
        prev = bound
    return "\n".join(rows)


CHAINS = {
    "A": ("qwen3_1_7b", "train_4k", [
        ("baseline (dense, FSDP+TP, full remat)", "", "dense"),
        ("+ flashvjp", "flashvjp", "dense"),
        ("+ rematdots", "flashvjp-rematdots", "dense"),
        ("(+ kvcol — REFUTED)", "flashvjp-kvcol", "dense"),
        ("(+ kvrep — REFUTED)", "flashvjp-rematdots-kvrep", "dense"),
        ("(+ block1024)", "flashvjp-rematdots-block1024", "dense"),
    ]),
    "B": ("deepseek_moe_16b", "train_4k", [
        ("baseline (einsum-dispatch EP over tp)", "", "dense"),
        ("+ moedff (TP-within-expert)", "moedff", "dense"),
        ("+ moesm (shard_map EP)", "moesm", "dense"),
        ("+ flashvjp + rematdots", "moesm-flashvjp-rematdots", "dense"),
    ]),
    "C": ("llama3_405b", "decode_32k", [
        ("baseline (dense bf16, FSDP serving)", "", "dense"),
        ("+ pinseq", "pinseq", "dense"),
        ("+ maskupd", "pinseq-maskupd", "dense"),
        ("+ 2D-TP serving", "pinseq-maskupd-2dtp", "dense"),
        ("+ int8 weights (paper LM_8b)", "pinseq-maskupd-2dtp",
         "serve_int8"),
        ("+ int8 KV cache (paper on KV)", "pinseq-maskupd-kv8-2dtp",
         "serve_int8"),
        ("+ int8 attention math",
         "pinseq-maskupd-kv8-attnint8-2dtp", "serve_int8"),
        ("(bit-packed weights, XLA-oracle)",
         "pinseq-maskupd-kv8-2dtp", "serve_packed"),
    ]),
}


def fleet_table():
    """Baseline vs fleet-optimized bound per (arch x shape) single-pod."""
    base = {(r["arch"], r["shape"]): r
            for r in load("results/dryrun")
            if not r.get("skipped") and r["mesh"] == "single"
            and not r.get("tag")}
    opt = {}
    for r in load("results/opt"):
        if r.get("skipped"):
            continue
        key = (r["arch"], r["shape"])
        if key not in opt or r["bound_s"] < opt[key]["bound_s"]:
            opt[key] = r
    # per-arch flag choice: if every opt set regresses a cell, production
    # ships with the flags off — the baseline is a candidate.
    for key, b in base.items():
        if key in opt and opt[key]["bound_s"] > b["bound_s"]:
            keep = dict(b)
            keep["tag"] = "baseline kept (opts regress)"
            opt[key] = keep
    rows = ["| arch | shape | baseline bound ms | optimized bound ms | "
            "speedup | frac before | frac after | opt set |",
            "|---|---|---|---|---|---|---|---|"]
    for key in sorted(opt):
        b, o = base.get(key), opt[key]
        if b is None:
            continue
        tag = o.get("tag", "") + (" +int8w" if o["weights"] != "dense" else "")
        rows.append(
            f"| {key[0]} | {key[1]} | {b['bound_s']*1e3:.1f} "
            f"| {o['bound_s']*1e3:.1f} | {b['bound_s']/o['bound_s']:.2f}x "
            f"| {b['roofline_fraction']:.3f} | {o['roofline_fraction']:.3f} "
            f"| {tag} |")
    import math
    sp = [base[k]["bound_s"] / opt[k]["bound_s"] for k in opt if k in base]
    fr = [opt[k]["roofline_fraction"] for k in opt if k in base]
    if sp:
        gm = math.exp(sum(math.log(s) for s in sp) / len(sp))
        gf = math.exp(sum(math.log(max(f, 1e-9)) for f in fr) / len(fr))
        rows.append(f"| **GEOMEAN** | {len(sp)} cells | | | **{gm:.2f}x** "
                    f"| | **{gf:.3f}** | |")
    return "\n".join(rows)


def splice(text, marker, content):
    begin, end = f"<!-- {marker} -->", f"<!-- /{marker} -->"
    block = f"{begin}\n{content}\n{end}"
    if begin in text and end in text:
        return re.sub(re.escape(begin) + ".*?" + re.escape(end), block,
                      text, flags=re.S)
    return text.replace(f"<!-- {marker} -->", block)


def main():
    path = os.path.join(ROOT, "EXPERIMENTS.md")
    with open(path) as f:
        text = f.read()
    text = splice(text, "ROOFLINE_TABLE", roofline_table())
    for key, (arch, shape, steps) in CHAINS.items():
        text = splice(text, f"PERF_CHAIN_{key}",
                      chain_table(arch, shape, steps))
    text = splice(text, "FLEET_TABLE", fleet_table())
    with open(path, "w") as f:
        f.write(text)
    print("EXPERIMENTS.md regenerated")


if __name__ == "__main__":
    main()
