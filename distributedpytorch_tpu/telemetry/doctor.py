"""``dptpu-doctor``: read a run dir, tell the operator what happened.

The diagnosis layer over the flight recorder: load the stitched
timeline (:mod:`telemetry.timeline`), break down where the wall-clock
went, list every episode with its recovery time, and raise **findings**
— anomalies with the exact config knob or CLI remedy, in the feed
governor's recommendation idiom (a finding that does not name its fix
is a shrug, not a diagnosis).  Optionally folds in a live replica's
``/metrics`` text (``--metrics URL-or-file``) so serve-side counters
(swap outcomes, dropped telemetry deltas) join the verdict.

Findings carry a severity: ``info`` (observation), ``warning``
(degraded but recovered), ``critical`` (unresolved — the run needs a
human or a config change).  The process exits non-zero when any
critical finding stands, so the doctor can gate CI and chaos scenarios;
``--json`` emits the full report for machines.

Stdlib only, importable pre-jax: a dead run dir must be diagnosable
from any machine, no accelerator stack required.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

from .timeline import Timeline, load_timeline

#: finding severities, escalation order
SEVERITIES = ("info", "warning", "critical")

#: default thresholds the anomaly detectors judge against (each finding
#: names the threshold it tripped so the verdict is reproducible)
THRESHOLDS = {
    # wall-clock between events with nothing booked against it
    "unbooked_gap_s": 120.0,
    # repeated canary rollbacks without a promote in between
    "canary_rollbacks": 2,
    # quarantined batches across the run
    "quarantine_batches": 8,
    # sentinel rollbacks across the run
    "rollbacks": 3,
}


def _finding(severity: str, code: str, message: str, remedy: str,
             **detail) -> dict:
    assert severity in SEVERITIES
    return {"severity": severity, "code": code, "message": message,
            "remedy": remedy, "detail": detail}


def _read_jsonl(path: str) -> list[dict]:
    out = []
    try:
        with open(path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if isinstance(rec, dict):
                    out.append(rec)
    except OSError:
        pass
    return out


def _fit_summaries(path: str) -> list[tuple[str, dict]]:
    out = []
    for rd in [path] + sorted(glob.glob(os.path.join(path, "run_*"))):
        p = os.path.join(rd, "fit_summary.json")
        try:
            with open(p) as f:
                out.append((os.path.basename(rd) or rd, json.load(f)))
        except (OSError, ValueError):
            continue
    return out


def parse_metrics_text(text: str) -> dict[str, float]:
    """Prometheus 0.0.4 text -> ``{'name{labels}': value}``; quantile
    and comment lines keep their exact exposition key so callers can
    select with plain substring checks."""
    out: dict[str, float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        try:
            key, val = line.rsplit(" ", 1)
            out[key] = float(val)
        except ValueError:
            continue
    return out


def fetch_metrics(source: str) -> dict[str, float]:
    """``--metrics``: a file path or an ``http(s)://`` URL (a live
    replica's ``GET /metrics``)."""
    if source.startswith(("http://", "https://")):
        import urllib.request

        with urllib.request.urlopen(source, timeout=10) as resp:
            return parse_metrics_text(resp.read().decode("utf-8"))
    with open(source) as f:
        return parse_metrics_text(f.read())


def _metric_total(metrics: dict[str, float], name: str) -> float:
    return sum(v for k, v in metrics.items()
               if k == name or k.startswith(name + "{"))


# ------------------------------------------------------------- analysis

def goodput_breakdown(tl: Timeline) -> dict:
    """Aggregate the per-generation goodput blocks off the ``fit_end``
    anchors: summed buckets, the overall productive fraction, and the
    top wall-clock sinks (largest non-step buckets first)."""
    buckets: dict[str, float] = {}
    total = 0.0
    fits = 0
    for ev in tl.events:
        if ev["source"] != "trainer" or ev["kind"] != "fit_end":
            continue
        gp = ev["payload"].get("goodput") or {}
        if not gp.get("buckets"):
            continue
        fits += 1
        total += gp.get("total_s") or 0.0
        for b, v in gp["buckets"].items():
            if v is not None:
                buckets[b] = buckets.get(b, 0.0) + float(v)
    sinks = sorted(((b, s) for b, s in buckets.items() if b != "step"),
                   key=lambda kv: -kv[1])
    return {
        "fits": fits,
        "total_s": round(total, 3),
        "buckets": {b: round(s, 3) for b, s in buckets.items()},
        "productive_frac": (round(buckets.get("step", 0.0) / total, 4)
                            if total > 0 else None),
        "top_sinks": [{"bucket": b, "seconds": round(s, 3)}
                      for b, s in sinks[:3]],
    }


def trace_summaries(tl: Timeline) -> list[dict]:
    """Every ``trace_summary`` a capture left on the timeline
    (:class:`telemetry.trace.TraceCapture`): where the trace is, and device
    milliseconds per step by layer."""
    return [dict(e.get("payload") or {}, step=e.get("step"))
            for e in tl.events
            if e["source"] == "telemetry" and e["kind"] == "trace_summary"]


def detect_findings(tl: Timeline, path: str,
                    metrics: dict[str, float] | None = None,
                    thresholds: dict | None = None) -> list[dict]:
    th = dict(THRESHOLDS)
    th.update(thresholds or {})
    findings: list[dict] = []

    if not tl.events:
        findings.append(_finding(
            "warning", "no_events",
            f"no flight-recorder events under {path}",
            "run with telemetry=true (config) so run_dir/events/ is "
            "written; pre-recorder runs can only be read via their "
            "per-subsystem ledgers"))
        return findings

    # --- unresolved episodes (the critical class) ----------------------
    for ep in tl.episodes:
        if ep["resolved"]:
            continue
        code = f"unresolved_{ep['type']}"
        remedy = {
            "divergence_rollback":
                "rollback never replayed: check sentinel.max_rollbacks "
                "(budget may be exhausted) and quarantine.jsonl for the "
                "poisoned window",
            "stall_ladder":
                "input stall armed and never drained: raise "
                "data.max_echo, enable data.device_augment, or pack the "
                "source (dptpu-pack) per the governor's "
                "pack_recommendation",
            "preempt_resume":
                "preemption without a resumed generation: run under "
                "dptpu-supervise (restart_on_preempt) or resume=auto "
                "the next run manually",
            "crash_restart":
                "crash without a restart: check supervisor.jsonl for "
                "gave_up and raise --max-restarts if the budget ended "
                "the storm",
            "topology_replan":
                "topology changed but no replanned generation fit: "
                "launch with parallel.strategy=auto so the restart "
                "re-resolves its plan",
            "canary":
                "canary admitted but never decided: call promote() or "
                "rollback(), or lower promote_after so observation "
                "traffic decides it",
            "flywheel_cycle":
                "flywheel cycle left open: check flywheel.jsonl",
            "replica_kill":
                "replica declared dead and never rejoined the ring: "
                "check its <slot>.log in the fleet workdir, and the "
                "--max-restarts budget (a spent budget stops the "
                "respawns; /fleet/plan still counts the lost capacity)",
        }[ep["type"]]
        findings.append(_finding(
            "critical", code,
            f"{ep['type']} episode opened at t={ep['start']:.3f} "
            f"(generation {ep['generation']}) and never resolved",
            remedy, episode=ep))

    # --- stall above target at end of run ------------------------------
    last_gov = None
    for ev in tl.events:
        if ev["source"] == "governor":
            last_gov = ev
    if last_gov is not None:
        stall = last_gov["payload"].get("stall")
        target = last_gov["payload"].get("target")
        if (stall is not None and target is not None and stall > target
                and last_gov["kind"] != "disarm_echo"):
            findings.append(_finding(
                "warning", "stall_above_target",
                f"final governor reading has input_wait fraction "
                f"{stall:.4f} above target {target} "
                f"(last action: {last_gov['kind']})",
                "the run ended feed-bound: pack the source (dptpu-pack), "
                "raise data.max_echo, or enable data.device_augment / "
                "data.device_guidance",
                stall=stall, target=target, action=last_gov["kind"]))

    # --- rollback budget burn ------------------------------------------
    rollbacks = [e for e in tl.events
                 if e["source"] == "sentinel" and e["kind"] == "rollback"]
    if len(rollbacks) >= th["rollbacks"]:
        findings.append(_finding(
            "warning", "rollback_budget_burn",
            f"{len(rollbacks)} sentinel rollbacks (threshold "
            f"{th['rollbacks']}) — the run is burning its rollback "
            "budget",
            "inspect quarantine.jsonl for the poisoned inputs; if the "
            "divergence is numeric (not data), lower optim.lr or raise "
            "sentinel.diverged_factor",
            rollbacks=len(rollbacks)))

    # --- quarantine growth ---------------------------------------------
    quarantined = 0
    for rd in [path] + sorted(glob.glob(os.path.join(path, "run_*"))):
        for rec in _read_jsonl(os.path.join(rd, "quarantine.jsonl")):
            quarantined += len(rec.get("batch_indices") or [])
    if quarantined >= th["quarantine_batches"]:
        findings.append(_finding(
            "warning", "quarantine_growth",
            f"{quarantined} batches quarantined across the run "
            f"(threshold {th['quarantine_batches']})",
            "the skip set is eating the dataset: fix the poisoned "
            "records (dptpu-pack --verify names torn ones) or clear "
            "data.pack_quarantine after repair",
            quarantined_batches=quarantined))

    # --- repeated canary rollbacks -------------------------------------
    rb_run = 0
    for ep in tl.episodes:
        if ep["type"] != "canary" or not ep["resolved"]:
            continue
        if ep["detail"].get("outcome") == "rolled_back":
            rb_run += 1
        else:
            rb_run = 0
    if rb_run >= th["canary_rollbacks"]:
        findings.append(_finding(
            "warning", "repeated_canary_rollbacks",
            f"{rb_run} consecutive canary rollbacks without a promote",
            "every new generation is failing its canary: raise the "
            "flywheel's --min-improvement (weed out marginal fits) and "
            "check the fit sentinel/quarantine evidence before the next "
            "swap",
            consecutive_rollbacks=rb_run))

    # --- unexplained generation gaps -----------------------------------
    # between one generation's last event and the next generation's
    # first, time should be booked by a supervisor classify->spawn pair;
    # a long silent gap is unbooked wall-clock
    gen_events: dict[int, list[dict]] = {}
    for ev in tl.events:
        g = ev.get("generation")
        if g is not None and ev["source"] != "supervisor":
            gen_events.setdefault(g, []).append(ev)
    gens = sorted(gen_events)
    for a, b in zip(gens, gens[1:]):
        t_end = gen_events[a][-1]["t"]
        t_start = gen_events[b][0]["t"]
        gap = t_start - t_end
        if gap < th["unbooked_gap_s"]:
            continue
        explained = any(
            e["source"] == "supervisor" and t_end <= e["t"] <= t_start
            for e in tl.events)
        if not explained:
            findings.append(_finding(
                "critical", "unexplained_generation_gap",
                f"{gap:.1f}s of unbooked wall-clock between generation "
                f"{a} and {b} with no supervisor event explaining it "
                f"(threshold {th['unbooked_gap_s']}s)",
                "the run restarted outside supervision: launch under "
                "dptpu-supervise so restarts are classified and booked",
                gap_s=round(gap, 1), from_generation=a, to_generation=b))

    # --- last generation never finished --------------------------------
    starts = [e for e in tl.events
              if e["source"] == "trainer" and e["kind"] == "fit_start"]
    ends = [e for e in tl.events
            if e["source"] == "trainer" and e["kind"] == "fit_end"]
    if starts:
        last_gen = starts[-1].get("generation")
        ended = any(e.get("generation") == last_gen for e in ends)
        sup_closed = any(
            s.get("event") in ("clean_exit", "clean_exit_unverified")
            for s in tl.supervisor)
        if not ended and not sup_closed:
            findings.append(_finding(
                "critical", "run_incomplete",
                f"generation {last_gen} opened a fit and never closed "
                "it, and no supervisor clean_exit explains the end",
                "the last process died mid-fit: resume with resume=auto "
                "(the COMMITTED ledger names the restart step) or run "
                "under dptpu-supervise",
                generation=last_gen))

    # --- dropped telemetry deltas (live /metrics) ----------------------
    if metrics:
        dropped = _metric_total(metrics, "telemetry_dropped_deltas_total")
        if dropped > 0:
            findings.append(_finding(
                "warning", "dropped_telemetry_deltas",
                f"{int(dropped)} negative goodput deltas dropped "
                "(telemetry_dropped_deltas_total) — a clock reset or "
                "accountant reset raced the feed window",
                "benign once per fit start; a growing count means "
                "something resets the accountant mid-fit — check for "
                "concurrent fits sharing the process",
                dropped=dropped))
        swap_rb = _metric_total(
            metrics, "serve_swaps_total")
        if swap_rb:
            findings.append(_finding(
                "info", "serve_swaps_observed",
                f"{int(swap_rb)} swap decisions on the live replica",
                "no action needed; see the canary episodes for outcomes",
                swaps=swap_rb))

    order = {s: i for i, s in enumerate(SEVERITIES)}
    findings.sort(key=lambda f: -order[f["severity"]])
    return findings


def diagnose(path: str, metrics: dict[str, float] | None = None,
             thresholds: dict | None = None) -> dict:
    """The full report: timeline + goodput + episodes + findings +
    verdict.  ``verdict`` is the highest standing severity ('healthy'
    when no finding stands)."""
    tl = load_timeline(path)
    findings = detect_findings(tl, path, metrics=metrics,
                               thresholds=thresholds)
    worst = "healthy"
    for f in findings:
        if f["severity"] == "critical":
            worst = "critical"
            break
        if f["severity"] == "warning":
            worst = "warning"
    return {
        "path": path,
        "verdict": worst,
        "timeline": tl.to_dict(),
        "goodput": goodput_breakdown(tl),
        "traces": trace_summaries(tl),
        "fit_summaries": [name for name, _ in _fit_summaries(path)],
        "findings": findings,
    }


# ------------------------------------------------------------ rendering

def render(report: dict) -> str:
    lines: list[str] = []
    tl = report["timeline"]
    add = lines.append
    add(f"dptpu-doctor: {report['path']}")
    add(f"verdict: {report['verdict'].upper()}")
    add(f"events: {tl['events_total']} across "
        f"{len(tl['files'])} file(s), generations {tl['generations']}, "
        f"span {tl['span_s']}s")
    if tl["by_source"]:
        add("  by source: " + ", ".join(
            f"{s}={n}" for s, n in sorted(tl["by_source"].items())))
    gp = report["goodput"]
    if gp["fits"]:
        add(f"goodput: {gp['productive_frac']} productive over "
            f"{gp['total_s']}s ({gp['fits']} fit(s))")
        for sink in gp["top_sinks"]:
            add(f"  sink: {sink['bucket']:<12} {sink['seconds']}s")
    for tr in report.get("traces", ()):
        layers = ", ".join(
            f"{k} {v:.2f}" for k, v in sorted(
                (tr.get("ms_per_step_by_layer") or {}).items(),
                key=lambda kv: -kv[1]))
        add(f"trace: {tr.get('trace_dir')} ({tr.get('steps')} steps"
            f"{', STALE table' if tr.get('stale') else ''}) "
            f"ms/step by layer: {layers}")
    add(f"episodes: {len(tl['episodes'])}")
    for ep in tl["episodes"]:
        state = "resolved" if ep["resolved"] else "UNRESOLVED"
        rec = (f", recovery {ep['recovery_s']}s"
               if ep.get("recovery_s") is not None else "")
        add(f"  [{state}] {ep['type']} gen={ep['generation']}"
            f"{rec} ({len(ep['events'])} events)")
    if tl["orphans"]:
        add(f"orphan events: {len(tl['orphans'])}")
        for o in tl["orphans"]:
            add(f"  seq={o['seq']} {o['source']}/{o['kind']} "
                f"gen={o['generation']}")
    add(f"findings: {len(report['findings'])}")
    for f in report["findings"]:
        add(f"  [{f['severity'].upper()}] {f['code']}: {f['message']}")
        add(f"    remedy: {f['remedy']}")
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="dptpu-doctor",
        description="diagnose a run dir from its flight-recorder "
                    "timeline")
    ap.add_argument("path", help="run dir or supervisor work dir")
    ap.add_argument("--metrics", default=None,
                    help="Prometheus text to fold in: a file path or a "
                         "live replica's /metrics URL")
    ap.add_argument("--json", action="store_true",
                    help="emit the machine report instead of text")
    ap.add_argument("--threshold", action="append", default=[],
                    metavar="KEY=VALUE",
                    help=f"override an anomaly threshold "
                         f"(one of {sorted(THRESHOLDS)})")
    args = ap.parse_args(argv)
    thresholds = {}
    for kv in args.threshold:
        k, _, v = kv.partition("=")
        if k not in THRESHOLDS:
            ap.error(f"unknown threshold {k!r} "
                     f"(one of {sorted(THRESHOLDS)})")
        thresholds[k] = float(v)
    metrics = fetch_metrics(args.metrics) if args.metrics else None
    report = diagnose(args.path, metrics=metrics, thresholds=thresholds)
    if args.json:
        print(json.dumps(report, indent=2, allow_nan=False))
    else:
        print(render(report))
    # non-zero on critical findings: the CI / chaos gate
    return 1 if report["verdict"] == "critical" else 0


if __name__ == "__main__":
    sys.exit(main())
