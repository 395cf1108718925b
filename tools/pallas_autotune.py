"""Autotune populate pass: measured PALLASBENCH.json geometry rows ->
per-(op, shape) impl overrides in PALLAS_TUNE.json.

PR 8 fused the non-conv analyzer stages into Pallas and made their
dispatch consult ``ops/pallas/tuning.lookup_impl(op, **dims)`` -- but the
geometry rows in PALLASBENCH.json carried ANALYTIC rooflines only
(``pallas_ms: null``), so the table never got populated. This tool closes
that loop: when ``bench_pallas.py`` has written measured ``pallas_ms`` /
``xla_ms`` for the geometry ops, it decides per (op, shape) which backend
actually wins (same >3% margin criterion as the conv autotuner -- inside
the noise band no override is written and the caller's default policy
runs) and writes the overrides ``resolve_impl`` reads.

Row hygiene mirrors ``tuning.lookup_impl``: a malformed row (missing
dims, non-numeric or non-positive timing, unknown op) is REJECTED with a
reason, never trusted; a bad
bench file must not turn into a serving-time dispatch veto.

Usage:
    python tools/pallas_autotune.py                 # write PALLAS_TUNE.json
    python tools/pallas_autotune.py --dry-run       # diff only, no write
    python tools/pallas_autotune.py --bench other.json --margin 0.05
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from robotic_discovery_platform_tpu.ops.pallas import tuning  # noqa: E402

#: bench row "op" -> (tune-table op as resolve_impl queries it, its dims)
GEOMETRY_OPS = {
    "deproject_edge_stats": ("deproject", ("h", "w", "stride")),
    "bspline_design": ("bspline_design", ("n", "c")),
    "bspline_curvature": ("bspline_curvature", ("n", "c")),
}

#: table-key prefixes this pass owns (stale geometry entries under these
#: prefixes are dropped on rewrite; conv3x3 tile entries are untouched)
_OWNED_PREFIXES = tuple(f"{op}:" for op, _ in GEOMETRY_OPS.values())

DEFAULT_MARGIN = 0.03  # same ">3% faster" criterion as `autotune` for conv


def _positive_ms(row: dict, key: str) -> float:
    v = row.get(key)
    if not isinstance(v, (int, float)) or isinstance(v, bool):
        raise ValueError(f"{key} is {v!r}, not a number")
    v = float(v)
    if not math.isfinite(v) or v <= 0.0:
        # a zero or negative time is a broken measurement: reject, never
        # treat as "infinitely fast"
        raise ValueError(f"{key}={v} is not a positive finite time")
    return v


def extract_overrides(
    bench: dict, margin: float = DEFAULT_MARGIN
) -> tuple[dict, list[str]]:
    """(entries, rejected_reasons) from one PALLASBENCH.json payload.

    Entries carry both measured times so the table stays self-documenting
    evidence, exactly like the conv autotuner's entries."""
    entries: dict[str, dict] = {}
    rejected: list[str] = []
    rows = bench.get("geometry")
    if rows is None:
        rejected.append("no 'geometry' section in bench payload")
        return entries, rejected
    if not isinstance(rows, list):
        rejected.append(f"'geometry' section is {type(rows).__name__}, "
                        "not a row list")
        return entries, rejected
    for i, row in enumerate(rows):
        where = f"geometry[{i}]"
        if not isinstance(row, dict):
            rejected.append(f"{where}: not an object")
            continue
        op = row.get("op")
        if op not in GEOMETRY_OPS:
            rejected.append(f"{where}: unknown op {op!r}")
            continue
        table_op, dim_names = GEOMETRY_OPS[op]
        try:
            dims = {}
            for d in dim_names:
                v = row.get(d)
                if not isinstance(v, int) or isinstance(v, bool):
                    raise ValueError(f"dim {d!r} is {v!r}, not an int")
                dims[d] = v
            pallas_ms = _positive_ms(row, "pallas_ms")
            xla_ms = _positive_ms(row, "xla_ms")
        except ValueError as exc:
            rejected.append(f"{where} ({op}): {exc}")
            continue
        if pallas_ms < (1.0 - margin) * xla_ms:
            impl = "pallas"
        elif xla_ms < (1.0 - margin) * pallas_ms:
            impl = "xla"
        else:
            continue  # inside the noise band: no override, default policy
        entries[tuning.op_key(table_op, **dims)] = {
            "impl": impl,
            "pallas_ms": round(pallas_ms, 4),
            "xla_ms": round(xla_ms, 4),
            "speedup": round(xla_ms / pallas_ms, 3),
        }
    return entries, rejected


def merge_table(existing: dict, new_entries: dict) -> dict:
    """New table contents: every geometry-owned key is replaced by this
    pass's verdict (including DROPPING a stale override whose shape now
    measures inside the noise band); everything else -- the conv3x3 tile
    entries -- rides along untouched."""
    merged = {
        k: v for k, v in existing.items()
        if not k.startswith(_OWNED_PREFIXES)
    }
    merged.update(new_entries)
    return merged


def diff_tables(old: dict, new: dict) -> dict:
    added = sorted(set(new) - set(old))
    removed = sorted(set(old) - set(new))
    changed = sorted(
        k for k in set(new) & set(old) if new[k] != old[k]
    )
    return {"added": added, "removed": removed, "changed": changed}


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        description="Populate PALLAS_TUNE.json geometry impl overrides "
                    "from measured bench_pallas.py rows."
    )
    parser.add_argument("--bench", default=str(REPO / "PALLASBENCH.json"),
                        help="bench result file (default PALLASBENCH.json)")
    parser.add_argument("--margin", type=float, default=DEFAULT_MARGIN,
                        help="required win margin before an override is "
                             "written (default 0.03 = >3%%, the conv "
                             "autotuner's criterion)")
    parser.add_argument("--dry-run", action="store_true",
                        help="print the table diff and write nothing")
    cli = parser.parse_args(argv)

    try:
        bench = json.loads(Path(cli.bench).read_text())
    except (FileNotFoundError, json.JSONDecodeError) as exc:
        print(json.dumps({
            "error": "bench_unreadable",
            "detail": f"{type(exc).__name__}: {exc}",
            "bench": cli.bench,
        }))
        return 1

    entries, rejected = extract_overrides(bench, cli.margin)
    for reason in rejected:
        print(f"# rejected row: {reason}", file=sys.stderr)

    existing = dict(tuning._table())
    merged = merge_table(existing, entries)
    diff = diff_tables(existing, merged)

    summary = {
        "geometry_overrides": len(entries),
        "rejected_rows": len(rejected),
        "table_entries": len(merged),
        "dry_run": bool(cli.dry_run),
        **{k: len(v) for k, v in diff.items()},
    }
    if cli.dry_run:
        for k in diff["added"]:
            print(f"# + {k} -> {merged[k]}", file=sys.stderr)
        for k in diff["changed"]:
            print(f"# ~ {k}: {existing[k]} -> {merged[k]}",
                  file=sys.stderr)
        for k in diff["removed"]:
            print(f"# - {k} (was {existing[k]})", file=sys.stderr)
        print(json.dumps({**summary, "diff": diff}))
        return 0

    meta = {}
    try:
        meta = json.loads(tuning._TUNE_PATH.read_text()).get("meta", {})
    except (FileNotFoundError, json.JSONDecodeError):
        pass
    meta["geometry_autotune"] = {
        "source": cli.bench,
        "criterion": f">{cli.margin * 100:g}% faster than the other impl",
        "rejected_rows": len(rejected),
        "written_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    path = tuning.save_entries(merged, meta)
    print(f"# wrote {path}", file=sys.stderr)
    print(json.dumps({**summary, "path": str(path), "diff": diff}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
