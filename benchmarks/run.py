"""Benchmark entry point. One function per paper table + framework
benches. Prints ``name,us_per_call,derived`` CSV and writes one
``BENCH_<suite>.json`` artifact per suite at the repo root (DESIGN.md
§15): a stable schema — suite name, config, wall time, the parsed CSV
rows, and any pass/fail gate tokens found in the derived columns — so
CI and regression tooling diff machine-readable results instead of
scraping stdout. ``--no-artifacts`` restores print-only behaviour.

  PYTHONPATH=src python -m benchmarks.run [--only table1,...]
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import pathlib
import re
import sys
import time

import jax

from repro.launch.cache import use_compile_cache

from . import (
    bench_compaction,
    bench_device_serving,
    bench_dimensionality,
    bench_guidance,
    bench_kernels,
    bench_planning,
    bench_precision,
    bench_score_eval,
    bench_serving,
    bench_sharded_sampling,
    bench_solver_zoo,
    bench_tolerance_tiers,
    table1_solver_grid,
    table2_highdim,
    table3_offtheshelf,
    table45_ablations,
)

SUITES = {
    "table1": table1_solver_grid.main,     # paper Table 1 (+IS table analog)
    "table2": table2_highdim.main,         # paper Table 2
    "table3": table3_offtheshelf.main,     # paper Table 3 / App. A
    "table45": table45_ablations.main,     # paper Tables 4-5 / App. B
    "dimensionality": bench_dimensionality.main,  # beyond-paper
    "kernels": bench_kernels.main,
    "serving": bench_serving.main,
    "sharded_sampling": bench_sharded_sampling.main,  # 1-vs-N device scaling
    "compaction": bench_compaction.main,   # slot compaction vs monolithic
    "device_serving": bench_device_serving.main,  # host-sync traffic A/B
    "precision": bench_precision.main,     # fp32/bf16/bf16_full policies
    "guidance": bench_guidance.main,       # conditioning NFE overhead
    "planning": bench_planning.main,       # trajectory workload + planner loop
    "solver_zoo": bench_solver_zoo.main,   # zoo race + auto-selection report
    "score_eval": bench_score_eval.main,   # per-NFE hot-path roofline
    "tolerance_tiers": bench_tolerance_tiers.main,  # per-class NFE economics
}

#: artifacts land at the repo root, next to README.md — the stable,
#: diffable location CI uploads from
ROOT = pathlib.Path(__file__).resolve().parents[1]

# ``emit()`` rows: name,us_per_call,derived (derived may hold commas
# inside no row we produce, so a 2-split is exact)
_ROW_RE = re.compile(r"^([A-Za-z0-9_.\[\]/=:+-]+),([0-9.eE+-]+|),(.*)$")

#: derived-column tokens that read as benchmark gates — ``k=v`` where k
#: is a pass/fail flag (exact or ``*_pass``/``*_passed`` suffix)
_GATE_KEYS = {"pass", "passed", "compliant", "ok"}


def _parse_gates(derived: str):
    """Pull boolean gate tokens out of a derived column: ``k=v`` pieces
    (split on ``;`` / ``|``) whose key names a pass/fail check. Values
    parse as bool-ish (true/false/1/0/yes/no); anything else is skipped
    rather than guessed."""
    gates = {}
    for piece in re.split(r"[;|]", derived):
        piece = piece.strip()
        if "=" not in piece:
            continue
        k, v = piece.split("=", 1)
        k, v = k.strip(), v.strip().lower()
        if k in _GATE_KEYS or k.endswith("_pass") or k.endswith("_passed"):
            if v in ("true", "1", "yes"):
                gates[k] = True
            elif v in ("false", "0", "no"):
                gates[k] = False
    return gates


def parse_rows(text: str):
    """Parse a suite's captured stdout into structured rows: every
    ``name,us,derived`` CSV line becomes {name, us_per_call, derived,
    gates}; non-CSV lines (section banners, reports) are kept verbatim
    under ``notes`` so nothing a suite prints is dropped."""
    rows, notes = [], []
    for line in text.splitlines():
        m = _ROW_RE.match(line.strip())
        if m and not line.startswith("name,"):
            name, us, derived = m.groups()
            rows.append({
                "name": name,
                "us_per_call": float(us) if us else None,
                "derived": derived,
                "gates": _parse_gates(derived),
            })
        elif line.strip():
            notes.append(line.rstrip())
    return rows, notes


def artifact_path(name: str, out_dir: pathlib.Path = ROOT) -> pathlib.Path:
    """Where a suite's artifact lands: ``BENCH_<suite>.json`` at the
    repo root — the contract the artifact-coverage guard test pins."""
    return out_dir / f"BENCH_{name}.json"


def write_artifact(name: str, rows, notes, wall_time_s: float,
                   out_dir: pathlib.Path = ROOT) -> pathlib.Path:
    """One suite's machine-readable result (schema_version 1): name,
    config (argv + backend), wall time, parsed rows with their gate
    bits, and an aggregate ``gates`` rollup (all_pass over every gate
    token found)."""
    all_gates = {}
    for r in rows:
        for k, v in r["gates"].items():
            all_gates[f"{r['name']}:{k}"] = v
    doc = {
        "name": name,
        "schema_version": 1,
        "config": {
            "argv": sys.argv,
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
        },
        "wall_time_s": round(wall_time_s, 3),
        "rows": rows,
        "notes": notes,
        "gates": {
            "tokens": all_gates,
            "all_pass": all(all_gates.values()) if all_gates else None,
        },
    }
    path = artifact_path(name, out_dir)
    path.write_text(json.dumps(doc, indent=2) + "\n")
    return path


class _Tee(io.TextIOBase):
    """Mirror suite stdout to the real stream while capturing it for
    the artifact — the console output stays byte-identical."""

    def __init__(self, stream):
        self._stream = stream
        self._buf = io.StringIO()

    def write(self, s):
        self._stream.write(s)
        return self._buf.write(s)

    def flush(self):
        self._stream.flush()

    def getvalue(self) -> str:
        return self._buf.getvalue()


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names (default: all)")
    ap.add_argument("--no-artifacts", action="store_true",
                    help="print-only: skip the BENCH_<suite>.json files")
    args = ap.parse_args()
    names = args.only.split(",") if args.only else list(SUITES)

    print("name,us_per_call,derived")
    for name in names:
        if name not in SUITES:
            print(f"unknown suite {name}; have {list(SUITES)}", file=sys.stderr)
            raise SystemExit(2)
        t0 = time.time()
        tee = _Tee(sys.stdout)
        with contextlib.redirect_stdout(tee):
            SUITES[name]()
        wall = time.time() - t0
        if not args.no_artifacts:
            rows, notes = parse_rows(tee.getvalue())
            path = write_artifact(name, rows, notes, wall)
            print(f"# artifact {path.relative_to(ROOT)}", file=sys.stderr)
        print(f"# suite {name} done in {wall:.0f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
