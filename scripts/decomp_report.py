"""Render PERF_DECOMP.jsonl into the analysis table.

Reads the newest non-smoke row per (leg, depth) and prints:
  * the per-op forward+backward costs (op_s_*), each x8-blocks-per-layer
    context and as a share of the isolated trunk numbers;
  * the decomposition identities the measurement plan is built on
    (PERF.md): e2e ~= trunk_vg_s + geom_vg_s + optimizer, and
    trunk_vg_s/depth vs sum(op_s) (a lower bound — the reversible
    backward re-runs each op's forward once more for reconstruction);
  * device -> host transfer facts from the fetch_* rows (and the implied
    transfer share of any fetch-heavy twin that was also recorded).

Pure host-side text; run any time — it never touches the chip.
"""

from __future__ import annotations

import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
E2E_BASELINE_SEC = 24.41  # depth-12 e2e auto leg (PERF_SWEEP / PERF.md)


def latest_rows(path):
    rows = {}
    if not os.path.exists(path):
        return rows
    with open(path) as f:
        for line in f:
            try:
                e = json.loads(line)
            except ValueError:
                continue
            if e.get("smoke") or "error" in e:
                continue
            key = (e.get("leg") or e.get("metric"), e.get("depth"))
            rows[key] = e  # later lines win: newest measurement per leg
    return rows


def main():
    rows = latest_rows(os.path.join(REPO, "PERF_DECOMP.jsonl"))
    if not rows:
        print("no non-smoke rows in PERF_DECOMP.jsonl yet")
        return

    def sec(leg, depth=12):
        e = rows.get((leg, depth))
        return e["sec"] if e else None

    print(f"= decomposition (depth 12; e2e baseline {E2E_BASELINE_SEC} s) =")
    for (leg, depth), e in sorted(rows.items()):
        if leg.startswith(("fetch_", "profile")):
            continue
        print(f"  {leg:28s} d{depth:<3} {e['sec']:9.3f} s"
              + (f"   {e['tf_per_s']:6.1f} TF/s" if e.get("tf_per_s") else ""))

    ops = {leg: e["sec"] for (leg, depth), e in rows.items()
           if leg.startswith("op_s_") and depth == 12}
    tf12 = sec("trunk_fwd")
    tvg = sec("trunk_vg_s")
    gvg = sec("geom_vg_s")
    if ops:
        total = sum(ops.values())
        print(f"\n  sum(op_s fwd+bwd) = {total:.3f} s/layer-ish")
        if tvg:
            print(f"  trunk_vg_s/depth  = {tvg / 12:.3f} s  "
                  f"(>= sum(op_s)/ratio; reversible adds ~1 fwd for "
                  f"reconstruction)")
        for leg, s in sorted(ops.items(), key=lambda kv: -kv[1]):
            print(f"    {leg:26s} {s:7.3f} s  ({100 * s / total:5.1f}%)")
    if tf12 is not None:
        tf2 = sec("trunk_fwd", 2)
        print(f"\n  trunk_fwd d12 = {tf12:.3f} s ({tf12 / 12 * 1e3:.0f} "
              f"ms/layer vs ~61 ms analytic roofline)")
        if tf2 is not None:
            slope = (tf12 - tf2) / 10
            fixed = tf2 - 2 * slope
            print(f"  trunk_fwd d2  = {tf2:.3f} s -> marginal "
                  f"{slope * 1e3:.0f} ms/layer, fixed {fixed:.2f} s")
    if tvg and gvg:
        print(f"\n  identity: trunk_vg_s + geom_vg_s = {tvg + gvg:.2f} s "
              f"vs e2e {E2E_BASELINE_SEC} s "
              f"(gap = optimizer + composition effects)")

    fetches = {leg: e for (leg, depth), e in rows.items()
               if leg.startswith("fetch_")}
    if fetches:
        print("\n= device -> host transfer =")
        for leg, e in sorted(fetches.items()):
            rate = e.get("mb_per_s")
            print(f"  {leg:16s} {e['mb']:8.1f} MB in {e['sec']:8.4f} s"
                  + (f"  -> {rate:.1f} MB/s" if rate else ""))


if __name__ == "__main__":
    main()
