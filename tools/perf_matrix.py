"""Declarative bench config matrix: every BASELINE/ROADMAP milestone as
one runnable row.

Each row is a *declaration* — geometry + bench invocation + the
``perf_gate`` spec that judges it — so the owed on-chip backlog is a
mechanical sweep, not a hand-assembled sequence of bench commands:

    python tools/perf_matrix.py --list          # enumerate every row
    python tools/perf_matrix.py --run           # CPU-runnable subset
    python tools/perf_matrix.py --run --all     # everything (on-chip)
    python tools/perf_matrix.py --run --only offload_pipelined_ab

``--run`` executes each selected row's bench in a subprocess, parses
the LAST JSON line it prints (a bench prints exactly one record, or none
and a non-zero exit when it fails or finds no chip), gates it against any
matching-metric history records found in the repo's ``BENCH_*``/
``MULTICHIP_*`` files via :mod:`tools.perf_gate`, and prints one
verdict line per row plus a final JSON summary.  Rows whose capability
does not exist yet (MoE expert parallel, Ulysses long-sequence) are
EXPLICIT ``unavailable`` records — the matrix's coverage statement
includes what it cannot measure, so absence is visible instead of
silent (same contract as the memory ledger's ``unavailable_entry``).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import subprocess
import sys
import time
from dataclasses import dataclass, field
from typing import List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclass
class MatrixRow:
    """One milestone: a bench invocation plus the gate that judges it."""

    name: str
    milestone: str               # BASELINE config / ROADMAP item it covers
    metric: str                  # record metric the bench emits
    argv: List[str] = field(default_factory=list)   # after sys.executable
    cpu_ok: bool = False         # runnable on a chipless CPU host?
    cpu_note: str = ""           # why not, when cpu_ok is False
    unavailable_reason: Optional[str] = None  # capability doesn't exist
    timeout_s: float = 600.0


_NEEDS_CHIP = "the benches refuse to run without a TPU"

#: The matrix.  Geometry knobs live in the argv — a new milestone is a
#: new row, not a new driver.
ROWS: List[MatrixRow] = [
    MatrixRow(
        name="train_125m_zero1",
        milestone="BASELINE: GPT-2 125M, ZeRO-1, single chip",
        metric="train_tokens_per_sec_per_chip_gpt125m",
        argv=["bench.py"],
        cpu_ok=False,
        cpu_note="125M train engine on a 1-core host exceeds any honest "
                 "budget; headline numbers are chip numbers"),
    MatrixRow(
        name="train_paired_attention_ab",
        milestone="ROADMAP 2: head-paired flash attention vs folded "
                  "(honest d64 geometry)",
        metric="train_paired_attention_ab",
        argv=["bench.py", "--paired-ab"],
        cpu_ok=False,
        cpu_note="paired kernels are Mosaic/MXU programs; no CPU lowering"),
    MatrixRow(
        name="train_offload_pipelined_ab",
        milestone="ROADMAP 1: pipelined host-Adam vs synchronous "
                  "whole-tree offload boundary",
        metric="train_offload_pipelined_ab",
        argv=["bench.py", "--offload-ab"],
        cpu_ok=False,
        cpu_note=_NEEDS_CHIP),
    MatrixRow(
        name="train_7b_zero3_virtual_mesh",
        milestone="BASELINE: Llama-2 7B, ZeRO-3 + fused_adam, v5p-16",
        metric="train_tokens_per_sec_per_chip_gpt125m",
        argv=["bench.py"],
        cpu_ok=False,
        cpu_note="7B ZeRO-3 evidence rides in the headline bench's "
                 "memory-ledger entry (virtual_mesh/7b_zero3); "
                 "throughput itself needs the v5p mesh"),
    MatrixRow(
        name="fastgen_125m_decode",
        milestone="BASELINE: FastGen ragged-batch decode (125M-class "
                  "geometry)",
        metric="fastgen_decode_tokens_per_sec_125m",
        argv=["bench_serving.py"],
        cpu_ok=False,
        cpu_note=_NEEDS_CHIP,
        timeout_s=900.0),
    MatrixRow(
        name="fastgen_7b_int8",
        milestone="BASELINE: FastGen Llama-2 7B ragged inference, v5e-8",
        metric="fastgen_7b_int8_decode_tokens_per_sec",
        argv=["bench_serving.py", "--7b"],
        cpu_ok=False,
        cpu_note="7B weights + int8 matmul path sized for v5e HBM"),
    MatrixRow(
        name="serving_scheduler_goodput",
        milestone="ROADMAP: continuous-batch scheduler goodput "
                  "(decode A/B)",
        metric="serving_scheduler_goodput_tokens_per_sec",
        argv=["bench_serving.py", "--scheduler"],
        cpu_ok=False,
        cpu_note=_NEEDS_CHIP,
        timeout_s=900.0),
    MatrixRow(
        name="serving_session_mix",
        milestone="ROADMAP: session-mix capacity (int8 KV + host cold "
                  "tier)",
        metric="serving_session_mix_resident_sessions",
        argv=["bench_serving.py", "--session-mix"],
        cpu_ok=False,
        cpu_note=_NEEDS_CHIP,
        timeout_s=900.0),
    MatrixRow(
        name="serving_speculative",
        milestone="ROADMAP: speculative decode (draft-k acceptance)",
        metric="serving_speculative_decode_tokens_per_sec",
        argv=["bench_serving.py", "--speculative"],
        cpu_ok=False,
        cpu_note=_NEEDS_CHIP,
        timeout_s=900.0),
    MatrixRow(
        name="serving_fleet_disagg",
        milestone="ROADMAP: fleet scheduler + prefill/decode "
                  "disaggregation",
        metric="serving_fleet_goodput_tokens_per_sec",
        argv=["bench_serving.py", "--fleet", "2",
              "--disaggregate", "1:1"],
        cpu_ok=False,
        cpu_note=_NEEDS_CHIP,
        timeout_s=900.0),
    MatrixRow(
        name="serving_gateway_replayed_burst",
        milestone="ROADMAP: HTTP/SSE gateway + recorded-trace load "
                  "harness (2x replayed burst through admission "
                  "control)",
        metric="serving_gateway_replay_goodput_tokens_per_sec",
        argv=["tools/gateway_smoke.py", "--replay"],
        cpu_ok=True,
        timeout_s=600.0,
        unavailable_reason="recorded-trace replay numbers on CPU-host "
                           "tiny-Llama measure the harness, not the "
                           "serving stack — PERFLOG round 20 carries "
                           "them; the row goes live (drop this reason) "
                           "with the next TPU driver round, replaying "
                           "a chip-recorded trace against a real fleet"),
    MatrixRow(
        name="serving_elastic_soak",
        milestone="ROADMAP: live elastic capacity (real scale events "
                  "under traffic, graceful-drain downsize, brownout "
                  "degradation ladder)",
        metric="serving_elastic_soak_goodput_tokens_per_s",
        argv=["tools/elastic_smoke.py"],
        cpu_ok=True,
        timeout_s=600.0,
        unavailable_reason="diurnal-soak goodput on CPU-host tiny-Llama "
                           "measures the elastic machinery, not serving "
                           "capacity — PERFLOG round 21 carries the "
                           "measured scale-event latencies; the row "
                           "goes live (drop this reason) with the next "
                           "TPU driver round, soaking a chip-sized "
                           "fleet through real diurnal load"),
    MatrixRow(
        name="moe_mixtral_8x7b",
        milestone="BASELINE: DeepSpeed-MoE Mixtral-8x7B expert-parallel "
                  "all-to-all over ICI",
        metric="moe_expert_parallel_tokens_per_sec",
        unavailable_reason="expert-parallel all-to-all dispatch is not "
                           "implemented yet (ROADMAP: MoE direction); "
                           "tools/bench_moe_gemm.py covers only the "
                           "grouped-GEMM kernel"),
    MatrixRow(
        name="ulysses_64k_seqparallel",
        milestone="BASELINE: DeepSpeed-Ulysses Llama-2 7B 64k-seq on "
                  "v5p-64",
        metric="ulysses_seq_parallel_tokens_per_sec",
        unavailable_reason="sequence-parallel attention (head-sharded "
                           "all-to-all) is not implemented yet "
                           "(ROADMAP: long-context direction)"),
]


def _history_records(metric: str) -> List[dict]:
    """Matching-metric records from the repo's committed bench history
    (one JSON object per file; nested extras are not mined)."""
    out = []
    for pat in ("BENCH_*.json", "MULTICHIP_*.json", "BASELINE.json"):
        for path in sorted(glob.glob(os.path.join(REPO, pat))):
            try:
                rec = json.loads(open(path).read())
            except (OSError, ValueError):
                continue
            if isinstance(rec, dict) and rec.get("metric") == metric:
                out.append(rec)
    return out


def run_row(row: MatrixRow, verbose: bool = False) -> dict:
    """Execute one row end to end -> {row, status, record?, verdicts?}."""
    from perf_gate import KNOWN_RECORD_SPECS, gate

    base = {"row": row.name, "milestone": row.milestone,
            "metric": row.metric}
    if row.unavailable_reason is not None:
        return {**base, "status": "unavailable",
                "reason": row.unavailable_reason}
    argv = [sys.executable, os.path.join(REPO, row.argv[0]),
            *row.argv[1:]]
    t0 = time.monotonic()
    try:
        r = subprocess.run(argv, timeout=row.timeout_s,
                           capture_output=True, text=True, cwd=REPO)
    except subprocess.TimeoutExpired:
        return {**base, "status": "error",
                "reason": f"timed out after {row.timeout_s:.0f}s"}
    wall = round(time.monotonic() - t0, 1)
    if verbose and r.stderr:
        sys.stderr.write(r.stderr[-2000:])
    lines = [l for l in r.stdout.strip().splitlines() if l.strip()]
    try:
        record = json.loads(lines[-1])
    except (IndexError, ValueError):
        return {**base, "status": "error", "wall_s": wall,
                "reason": f"no JSON record on stdout (rc={r.returncode}): "
                          f"{r.stderr.strip()[-300:]}"}
    if "error" in record:
        return {**base, "status": "error", "wall_s": wall,
                "record": record, "reason": record["error"]}
    out = {**base, "status": "measured", "wall_s": wall,
           "record": record}
    history = _history_records(row.metric)
    specs = KNOWN_RECORD_SPECS.get(row.metric)
    if specs is None:
        out["gate"] = "skipped: no perf_gate spec for this metric"
    elif not history:
        out["gate"] = "no-history: record is the fresh baseline"
    else:
        ok, verdicts = gate(record, history, specs=specs)
        out["gate"] = "ok" if ok else "REGRESSED"
        out["verdicts"] = verdicts
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="perf_matrix",
        description="declarative BASELINE/ROADMAP bench matrix")
    ap.add_argument("--list", action="store_true",
                    help="enumerate every milestone row and exit")
    ap.add_argument("--run", action="store_true",
                    help="run the CPU-runnable subset (default) or "
                         "--all/--only selections")
    ap.add_argument("--all", action="store_true",
                    help="with --run: include chip-only rows too")
    ap.add_argument("--only", action="append", default=[],
                    metavar="NAME", help="run only the named row(s)")
    ap.add_argument("--out", default=None, metavar="DIR",
                    help="also write each row's record/verdict JSON here")
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args(argv)

    if args.list or not args.run:
        for row in ROWS:
            if row.unavailable_reason is not None:
                status = "unavailable"
            elif row.cpu_ok:
                status = "cpu-runnable"
            else:
                status = "chip-only"
            print(f"{row.name:32s} [{status}] {row.milestone}")
            if row.unavailable_reason:
                print(f"{'':34s}-> {row.unavailable_reason}")
            elif not row.cpu_ok and row.cpu_note:
                print(f"{'':34s}-> {row.cpu_note}")
        return 0

    unknown = [n for n in args.only if n not in {r.name for r in ROWS}]
    if unknown:
        raise SystemExit(f"perf_matrix: unknown row(s) {unknown}; "
                         f"see --list")
    selected = [r for r in ROWS
                if (r.name in args.only if args.only
                    else (args.all or r.cpu_ok
                          or r.unavailable_reason is not None))]
    results = []
    for row in selected:
        res = run_row(row, verbose=args.verbose)
        results.append(res)
        tag = res["status"] if res["status"] != "measured" \
            else f"measured gate={res.get('gate', '?')}"
        print(f"# {row.name}: {tag}"
              + (f" ({res['wall_s']}s)" if "wall_s" in res else ""),
              file=sys.stderr, flush=True)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            with open(os.path.join(args.out, f"{row.name}.json"),
                      "w") as f:
                json.dump(res, f, indent=1)
    regressed = [r["row"] for r in results
                 if r.get("gate") == "REGRESSED"]
    errored = [r["row"] for r in results if r["status"] == "error"]
    print(json.dumps({
        "perf_matrix": {
            "rows_run": len(results),
            "measured": sum(1 for r in results
                            if r["status"] == "measured"),
            "unavailable": sum(1 for r in results
                               if r["status"] == "unavailable"),
            "errors": errored,
            "regressed": regressed,
            "results": results,
        }}))
    return 1 if (regressed or errored) else 0


if __name__ == "__main__":
    sys.exit(main())
