"""Render README headline numbers from the newest bench artifact.

Rounds 2 and 3 both shipped a README whose hand-transcribed numbers
drifted from the measured BENCH_r*.json (55.7 vs 55.25 MFU, ~14s vs
17.3s recovery). This tool makes the claims block GENERATED: it
regex-extracts the headline keys from the newest ``BENCH_r*.json``
(the driver's capture may truncate the stored JSON, so no json.loads)
and rewrites the block between ``<!-- claims:begin -->`` and
``<!-- claims:end -->`` in README.md, citing the source file.
``tests/test_readme_claims.py`` asserts the rendered numbers match the
artifact they cite.

Usage::

    python tools/render_claims.py            # rewrite README.md
    python tools/render_claims.py --check    # exit 1 on drift
"""

import argparse
import glob
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BEGIN, END = "<!-- claims:begin -->", "<!-- claims:end -->"


def newest_artifact() -> str:
    """Newest artifact THAT HAS DATA.

    Driver artifacts (BENCH_r*.json) in numeric round order, newest
    first — but an empty capture (round 4's rc=124 artifact holds no
    keys) must not freeze the claims at an older round, so artifacts
    without a single extractable headline key are skipped. A
    bench-written BENCH_SELF.json (the full in-round measurement the
    driver's 2000-char tail would truncate) outranks driver artifacts
    when it is fresher than the newest of them."""
    files = glob.glob(os.path.join(REPO, "BENCH_r*.json"))

    # Numeric round order: lexicographic would put r10 before r9.
    def round_no(p):
        m = re.search(r"BENCH_r(\d+)\.json$", p)
        return int(m.group(1)) if m else -1

    def has_data(p):
        try:
            text = open(p).read()
        except OSError:
            return False
        return extract(text, "mfu_pct") is not None or extract(
            text, "measured_recovery_s"
        ) is not None or extract(text, "value") is not None

    ordered = sorted(files, key=round_no, reverse=True)
    newest_driver = next((p for p in ordered if has_data(p)), None)
    self_path = os.path.join(REPO, "BENCH_SELF.json")
    if os.path.exists(self_path) and has_data(self_path):
        if newest_driver is None or os.path.getmtime(
            self_path
        ) >= os.path.getmtime(newest_driver):
            return self_path
    if newest_driver is None:
        raise SystemExit("no artifact with data found")
    return newest_driver


def extract(text: str, key: str):
    m = re.search(rf'\\?"{key}\\?": ([-0-9.]+)', text)
    return float(m.group(1)) if m else None


def extract_str(text: str, key: str):
    m = re.search(rf'\\?"{key}\\?": \\?"([A-Za-z0-9_-]+)\\?"', text)
    return m.group(1) if m else None


def fmt(v, nd=2):
    if v is None:
        return "n/a"
    if float(v).is_integer() and nd != 0:
        return str(int(v))
    return f"{v:.{nd}f}".rstrip("0").rstrip(".")


def render_block(path: str) -> str:
    text = open(path).read()
    g = lambda k: extract(text, k)  # noqa: E731
    name = os.path.basename(path)
    # (label, gate key, formatted value) — rows whose gate key is
    # absent from the artifact are omitted rather than rendered "n/a".
    rows = [
        ("Flagship 334M training MFU (v5e, 6N basis)",
         g("mfu_pct"),
         f"{fmt(g('mfu_pct'))}%"),
        ("Long-context 32k single-chip (6N+attention MFU basis)",
         g("longctx_mfu_pct"),
         f"{fmt(g('longctx_tokens_per_s'), 0)} tok/s"
         f" / {fmt(g('longctx_mfu_pct'))}%"),
        ("Long-context 64k single-chip",
         g("longctx_mfu_pct_64k"),
         f"{fmt(g('longctx_tokens_per_s_64k'), 0)} tok/s"
         f" / {fmt(g('longctx_mfu_pct_64k'))}%"),
        ("Flash-attention speedup vs XLA (s=4096, fwd+bwd)",
         g("attn_pallas_speedup_s4096"),
         f"{fmt(g('attn_pallas_speedup_s4096'))}x"),
        ("Ring-attention inner block vs einsum (s=8192)",
         g("ring_inner_speedup_s8192"),
         f"{fmt(g('ring_inner_speedup_s8192'))}x"),
        ("Fused chunked CE vs dense (time ratio"
         + (
             f"; saves {fmt(g('ce_fused_logits_bytes_saved_mb'), 0)}"
             " MB logits"
             if g("ce_fused_logits_bytes_saved_mb") is not None
             else ""
         ) + ")",
         g("ce_fused_chunked_vs_dense"),
         f"{fmt(g('ce_fused_chunked_vs_dense'), 3)}x"),
        ("Checkpoint save pause (async snapshot block)",
         g("ckpt_save_block_s"),
         f"{fmt((g('ckpt_save_block_s') or 0) * 1e3, 1)} ms"),
        ("Measured SIGKILL recovery (detect+restart+restore+replay)",
         g("measured_recovery_s"),
         f"{fmt(g('measured_recovery_s'))} s"),
        ("— of which recovery machinery (excl. the state transfer)",
         g("e2e_machinery_recovery_s"),
         f"{fmt(g('e2e_machinery_recovery_s'))} s"),
        ("End-to-end goodput @ MTBF 3600s, autotuned cadence",
         g("e2e_goodput_pct"),
         f"{fmt(g('e2e_goodput_pct'))}%"
         " (reference claim: 95%)"),
        ("Decode (batch 8, 334M)",
         g("decode_ms_per_token"),
         f"{fmt(g('decode_ms_per_token'), 2)} ms/token"),
        ("Decode vs HBM roofline (spec BW; params+filled KV floor)",
         g("decode_vs_roofline"),
         f"{fmt(g('decode_vs_roofline'), 2)}x"),
        ("Profiler capture overhead (60s cadence)",
         g("profiler_overhead_pct"),
         f"{fmt(g('profiler_overhead_pct'), 3)}%"),
        # §33 raw-speed kernel campaign rows (absent until a bench
        # round measures them on hardware).
        # Gated on the artifact's RECORDED dispatch impl: pre-§33
        # artifacts (no key) and gmm A/B rounds both carry a
        # moe_dropless_mfu_active_pct that was NOT measured on the
        # fused kernel and must not render under its label.
        ("MoE dropless active-MFU (fused sort-dispatch kernel)",
         (g("moe_dropless_mfu_active_pct")
          if extract_str(text, "moe_dispatch_impl") == "fused"
          else None),
         f"{fmt(g('moe_dropless_mfu_active_pct'))}%"),
        ("Decode vs HBM roofline with int8 KV (batch 8)",
         g("decode_vs_roofline_int8"),
         f"{fmt(g('decode_vs_roofline_int8'), 2)}x"),
        ("Paged-KV effective slots, int8 at equal HBM",
         g("serving_kv_effective_slots_int8"),
         f"{fmt(g('serving_kv_effective_slots_int8'), 0)}"
         f" (fp16: {fmt(g('serving_kv_effective_slots'), 0)})"),
        ("Ring-attention overlap schedule speedup (s=8192)",
         g("ring_overlap_speedup_s8192"),
         f"{fmt(g('ring_overlap_speedup_s8192'), 3)}x"),
        # §35 speculative decoding row (absent until a bench round runs
        # the spec_decode phase): both campaign keys must be present —
        # tokens/step without the equal-slots serving speedup (or vice
        # versa) is a partial measurement that must not render.
        ("Self-spec decode: accepted tokens/verify-step "
         "(repetitive-suffix workload)",
         (g("spec_tokens_per_step")
          if g("spec_serving_speedup") is not None
          else None),
         f"{fmt(g('spec_tokens_per_step'))} tok/step"
         f" / {fmt(g('spec_serving_speedup'))}x serving,"
         f" equal slots"),
    ]
    origin = (
        "full in-round measurement written by bench.py"
        if name == "BENCH_SELF.json"
        else "driver-captured"
    )
    lines = [
        f"A DATED RECORD, not the state of this tree: measured on a "
        f"v5e on 2026-08-01, before PRs 1-20, and not re-measured "
        f"since — source: `{name}` ({origin}).",
        "",
        "| Metric | Measured |",
        "|---|---|",
    ]
    for label, gate, val in rows:
        if gate is not None:
            lines.append(f"| {label} | **{val}** |")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true")
    ns = ap.parse_args(argv)
    readme = os.path.join(REPO, "README.md")
    text = open(readme).read()
    if BEGIN not in text or END not in text:
        print("claims markers missing from README.md", file=sys.stderr)
        return 1
    block = render_block(newest_artifact())
    head, rest = text.split(BEGIN, 1)
    _, tail = rest.split(END, 1)
    new = f"{head}{BEGIN}\n{block}\n{END}{tail}"
    if ns.check:
        if new != text:
            print("README claims drift from the newest artifact — run "
                  "python tools/render_claims.py", file=sys.stderr)
            return 1
        return 0
    if new != text:
        open(readme, "w").write(new)
        print(f"README.md claims rendered from "
              f"{os.path.basename(newest_artifact())}")
    else:
        print("README.md already current")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
