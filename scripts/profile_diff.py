"""Compare two `scripts/profile_step.py` outputs (e.g. b8 vs b16) and name
what regressed ("explain b16 and b4 with the op profiles").

Raw HLO op names don't line up across batch sizes (XLA re-fuses and
renumbers: ``fusion.123`` at b8 is not ``fusion.123`` at b16), so the
stable comparison units are (1) the op *category* (convolution, fusion,
all-reduce, copy, ...) and (2) a fuzzy op key — the category plus the
name with trailing ``.N`` digits stripped.  Times are normalized
per-image (self_time / batch) so "regression" means what the batch table
means: more device time per unit of work.

Usage:  python scripts/profile_diff.py A.json B.json
  A/B are the JSON lines printed by profile_step.py (``--batch`` encoded
  in their "metric" field).  Prints one human table per comparison axis
  and one machine JSON line; values are always per-image normalized.
"""

from __future__ import annotations

import json
import re
import sys


def load(path: str) -> dict:
    with open(path) as f:
        txt = f.read().strip()
    # profile_step prints exactly one JSON object; tolerate tee'd noise
    # around it by grabbing the last line that parses.
    for line in reversed(txt.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    raise ValueError(f"no JSON record in {path}")


def batch_of(rec: dict) -> int:
    m = re.search(r"_b(\d+)_profile", rec.get("metric", ""))
    return int(m.group(1)) if m else 1


def fuzzy_key(op: dict) -> str:
    name = re.sub(r"[.\d]+$", "", str(op.get("op", "")))
    return f"{op.get('category', '')}:{name}"


def by(rows: list[dict], keyfn) -> dict[str, float]:
    agg: dict[str, float] = {}
    for r in rows:
        t = r.get("self_time_us")
        if isinstance(t, (int, float)):
            agg[keyfn(r)] = agg.get(keyfn(r), 0.0) + float(t)
    return agg


def table(title: str, a: dict[str, float], b: dict[str, float],
          na: str, nb: str, scale_a: float, scale_b: float) -> list[dict]:
    keys = sorted(set(a) | set(b),
                  key=lambda k: -(b.get(k, 0.0) * scale_b
                                  - a.get(k, 0.0) * scale_a))
    out = []
    print(f"\n== {title} (per-image us, {na} -> {nb}) ==")
    print(f"{'key':48s} {na:>10s} {nb:>10s} {'delta':>10s}")
    for k in keys:
        va, vb = a.get(k, 0.0) * scale_a, b.get(k, 0.0) * scale_b
        print(f"{k[:48]:48s} {va:10.1f} {vb:10.1f} {vb - va:+10.1f}")
        out.append({"key": k, na: round(va, 1), nb: round(vb, 1),
                    "delta": round(vb - va, 1)})
    return out


def main() -> None:
    paths = [a for a in sys.argv[1:] if not a.startswith("--")]
    if len(paths) != 2:
        sys.exit("usage: profile_diff.py A.json B.json")
    ra, rb = load(paths[0]), load(paths[1])
    ops_a = ra.get("top_ops_by_self_time") or []
    ops_b = rb.get("top_ops_by_self_time") or []
    if not ops_a or not ops_b:
        sys.exit(f"missing top_ops tables ({paths[0]}: {len(ops_a)} rows, "
                 f"{paths[1]}: {len(ops_b)} rows)")
    na, nb = f"b{batch_of(ra)}", f"b{batch_of(rb)}"
    if na == nb:
        # same-batch comparison (e.g. a score-dtype A/B at b8): distinct
        # column keys, or the output dicts would silently keep only B
        na, nb = na + "_a", nb + "_b"
    # per-image normalization; profile_step runs STEPS steps inside the
    # trace, identical for both captures, so steps cancel out.
    sa, sb = 1.0 / batch_of(ra), 1.0 / batch_of(rb)
    cats = table("by category", by(ops_a, lambda r: r["category"] or "?"),
                 by(ops_b, lambda r: r["category"] or "?"), na, nb, sa, sb)
    ops = table("by fuzzy op", by(ops_a, fuzzy_key), by(ops_b, fuzzy_key),
                na, nb, sa, sb)
    tot_a = sum(v for v in by(ops_a, lambda r: "t").values()) * sa
    tot_b = sum(v for v in by(ops_b, lambda r: "t").values()) * sb
    print(f"\ntotal top-op self time per image: {na} {tot_a:.1f} us, "
          f"{nb} {tot_b:.1f} us ({(tot_b / tot_a - 1) * 100:+.1f}%)")
    print(json.dumps({"a": paths[0], "b": paths[1],
                      "per_image_us": {na: round(tot_a, 1),
                                       nb: round(tot_b, 1)},
                      "by_category": cats, "top_regressions": ops[:8]}))


if __name__ == "__main__":
    main()
