#!/usr/bin/env python
"""blackbox_view — render a black-box postmortem bundle offline.

A bundle (written by `obs.dump_blackbox()` / `install_blackbox()` — see
sml_tpu/obs/blackbox.py) is a
directory of JSON artifacts from a crashed or stalled process. This
script turns it back into something a human debugs with:

- `trace.json` — the ring replayed through the SAME Chrome/Perfetto
  converter the live exporter uses (`sml_tpu/obs/_tracefmt.py`, loaded
  by FILE PATH: the graftlint pattern), including the causal flow
  arrows, ready for ui.perfetto.dev;
- a text summary — when (wall clock), why, what was in flight (with
  trace ids), which tickets stalled and where every thread was standing,
  the worst serving request by exemplar, the audit verdicts, and HBM
  occupancy.

STDLIB-ONLY and jax-free by construction (asserted in
tests/test_obs_forensics.py): the postmortem machine needs python,
nothing else.

Usage:
    python scripts/blackbox_view.py BUNDLE_DIR [--trace OUT.json]
"""

import argparse
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def _load_tracefmt():
    path = os.path.join(REPO, "sml_tpu", "obs", "_tracefmt.py")
    spec = importlib.util.spec_from_file_location("_bb_tracefmt", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_json(bundle, name):
    try:
        with open(os.path.join(bundle, name)) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def load_events(bundle):
    """(header args, event records) from events.jsonl; torn tail lines
    (the process may have died mid-write) are skipped, not fatal."""
    header, records = {}, []
    try:
        with open(os.path.join(bundle, "events.jsonl")) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("kind") == "meta":
                    header = rec.get("args") or {}
                else:
                    records.append(rec)
    except OSError:
        pass
    return header, records


def _fmt_unix(ts):
    if not ts:
        return "unknown"
    import datetime
    return datetime.datetime.fromtimestamp(
        ts, tz=datetime.timezone.utc).isoformat()


def summarize(bundle, header, records, manifest, metrics, audit,
              ledger) -> str:
    man = manifest or {}
    lines = [f"blackbox bundle: {bundle}",
             f"  reason:      {man.get('reason', header.get('reason'))}",
             f"  dumped:      {_fmt_unix(man.get('dumped_unix'))}",
             f"  epoch_unix:  {_fmt_unix(man.get('epoch_unix'))} "
             f"(= trace ts 0)",
             f"  version:     sml_tpu {man.get('sml_tpu_version', '?')}, "
             f"pid {man.get('pid', '?')}",
             f"  events:      {len(records)} in ring "
             f"({man.get('dropped_events', 0)} dropped)"]
    by_kind = {}
    for r in records:
        by_kind[r.get("kind", "?")] = by_kind.get(r.get("kind", "?"), 0) + 1
    lines.append("  by kind:     " + ", ".join(
        f"{k}={v}" for k, v in sorted(by_kind.items())))
    exc = man.get("exception")
    if exc:
        lines.append(f"---- exception: {exc.get('type')}: "
                     f"{exc.get('value')}")
        for ln in (exc.get("traceback") or [])[-6:]:
            lines.append(f"  {ln}")
    stalls = [r for r in records if r.get("name") == "stall.detected"]
    if stalls:
        lines.append(f"---- stalls ({len(stalls)} flagged)")
        for s in stalls:
            a = s.get("args") or {}
            lines.append(
                f"  {a.get('name')} [{a.get('kind')}] elapsed "
                f"{a.get('elapsed_s')}s (threshold "
                f"{a.get('threshold_s')}s) trace={a.get('trace')}")
            stacks = a.get("stacks") or {}
            for tname, frames in list(stacks.items())[:4]:
                lines.append(f"    {tname}:")
                for fr in frames[-3:]:
                    lines.append(f"      {fr}")
    inflight = man.get("inflight") or []
    if inflight:
        lines.append(f"---- in flight at dump ({len(inflight)} tickets)")
        for t in inflight:
            lines.append(
                f"  {t.get('name')} [{t.get('kind')}] "
                f"{t.get('elapsed_s')}s elapsed, "
                f"{'STALLED' if t.get('flagged') else 'ok'}, "
                f"trace={t.get('trace')} thread={t.get('thread')}")
    if metrics:
        req = (metrics.get("metrics") or {}).get("serve.request_ms")
        slo = metrics.get("slo") or {}
        if req:
            lines.append(
                f"---- serving: {req.get('count')} requests, p50 "
                f"{req.get('p50'):.3g}ms p99 {req.get('p99'):.3g}ms, "
                f"worst {slo.get('worst_ms')}ms "
                f"(trace {slo.get('worst_trace')}), SLO burn "
                f"{slo.get('burn_rate')}")
    if audit and audit.get("report"):
        lines.append("---- dispatch audit (tail)")
        for ln in audit["report"].splitlines()[:6]:
            lines.append(f"  {ln}")
    if ledger:
        lines.append("---- HBM ledger")
        for pool, v in sorted(ledger.items()):
            if isinstance(v, dict):
                lines.append(f"  {pool:<14} live {v.get('live', 0) / 1e6:8.1f} MB  "
                             f"peak {v.get('peak', 0) / 1e6:8.1f} MB")
    return "\n".join(lines)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="render a black-box postmortem bundle (trace.json + "
                    "text summary), jax-free")
    parser.add_argument("bundle", help="bundle directory "
                                       "(blackbox-<utc>-<pid>)")
    parser.add_argument("--trace", default=None,
                        help="Chrome trace output path (default: "
                             "<bundle>/trace.json)")
    args = parser.parse_args(argv)
    if not os.path.isdir(args.bundle):
        print(f"not a bundle directory: {args.bundle}", file=sys.stderr)
        return 2

    header, records = load_events(args.bundle)
    manifest = _load_json(args.bundle, "MANIFEST.json")
    metrics = _load_json(args.bundle, "metrics.json")
    audit = _load_json(args.bundle, "audit.json")
    ledger = _load_json(args.bundle, "ledger.json")

    tracefmt = _load_tracefmt()
    out = args.trace or os.path.join(args.bundle, "trace.json")
    doc = tracefmt.trace_doc(
        records,
        dropped=(manifest or {}).get("dropped_events", 0) or 0,
        epoch_unix=(manifest or {}).get("epoch_unix")
        or header.get("epoch_unix"),
        producer="scripts/blackbox_view.py")
    with open(out, "w") as f:
        json.dump(doc, f)

    print(summarize(args.bundle, header, records, manifest, metrics,
                    audit, ledger))
    print(f"trace written: {out} (open at https://ui.perfetto.dev)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
