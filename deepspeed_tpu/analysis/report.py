"""Findings, reports, suppression and baselines for the graft-lint pass.

Reference analogue: DeepSpeed surfaces comm behavior only at runtime
(``comms_logger``); here the lint result is a static artifact that CI can
diff. The report is JSON-serializable; a *baseline* is a previously-accepted
report digest — known findings are suppressed, and the recorded collective
census becomes an exact pin so a silently-added collective is a hard failure
even when no structural rule catches it.
"""

import dataclasses
import json
from typing import Any, Dict, List, Optional

SEVERITIES = ("error", "warning")


@dataclasses.dataclass
class Finding:
    rule: str                 # e.g. "collective-forbidden-kind"
    message: str
    severity: str = "error"
    program: str = ""         # which lowered program (train_step, ...)
    ident: str = ""           # stable discriminator within the rule
    nbytes: int = 0
    data: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def key(self) -> str:
        """Stable identity for suppression/baselines — survives reordering
        and byte-count drift."""
        return f"{self.rule}:{self.program}:{self.ident}"

    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["key"] = self.key
        return d


@dataclasses.dataclass
class Report:
    findings: List[Finding] = dataclasses.field(default_factory=list)
    suppressed: List[Finding] = dataclasses.field(default_factory=list)
    # {program_name: {kind: {"count": n, "bytes": b}}}
    census: Dict[str, Dict[str, Dict[str, int]]] = \
        dataclasses.field(default_factory=dict)
    # {program_name: {"overlapped"|"exposed": {"count": n, "bytes": b}}}
    # — scheduled-HLO overlap classification (analyzers.OverlapAudit)
    overlap: Dict[str, Dict[str, Dict[str, int]]] = \
        dataclasses.field(default_factory=dict)
    # {program_name: {"peak_hbm_bytes", "peak_breakdown", "state_bytes",
    #                 "boundary_activation_bytes", "remat", ...}}
    # — static peak-HBM liveness + memory-law measurement (MemoryLint)
    memory: Dict[str, Dict[str, Any]] = \
        dataclasses.field(default_factory=dict)
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return not any(f.severity == "error" for f in self.findings)

    def extend(self, findings: List[Finding]):
        self.findings.extend(findings)

    def suppress(self, patterns: List[str]):
        """Move findings whose key starts with any pattern (rule id or full
        key prefix) into the suppressed list."""
        if not patterns:
            return
        keep, drop = [], []
        for f in self.findings:
            (drop if any(f.key.startswith(p) or f.rule == p
                         for p in patterns) else keep).append(f)
        self.findings = keep
        self.suppressed.extend(drop)

    def apply_baseline(self, baseline: Dict[str, Any]):
        """Suppress findings recorded in an accepted baseline (by key)."""
        known = set(baseline.get("findings", ()))
        keep, drop = [], []
        for f in self.findings:
            (drop if f.key in known else keep).append(f)
        self.findings = keep
        self.suppressed.extend(drop)

    def baseline_dict(self) -> Dict[str, Any]:
        """Digest to accept the current state: every finding key (suppressing
        them next run) + the census counts (pinning them next run).

        Census-drift keys are NOT recorded: their key names only the op kind,
        so suppressing one would also suppress every FUTURE drift of that
        kind — defeating the exact pin. The recorded census re-pins the
        accepted counts instead."""
        keys = {f.key for f in self.findings} | {f.key for f in self.suppressed}
        return {
            "findings": sorted(k for k in keys
                               if not k.startswith("collective-census-drift:")),
            "census": {prog: {kind: dict(c) for kind, c in kinds.items()}
                       for prog, kinds in self.census.items()},
        }

    def to_dict(self) -> Dict[str, Any]:
        return {
            "ok": self.ok,
            "findings": [f.to_dict() for f in self.findings],
            "suppressed": [f.to_dict() for f in self.suppressed],
            "census": self.census,
            "overlap": self.overlap,
            "memory": self.memory,
            "meta": self.meta,
        }

    def to_json(self, indent: int = 2) -> str:
        return json.dumps(self.to_dict(), indent=indent, default=str)

    def summary(self) -> str:
        """Human-readable report."""
        lines = []
        for prog, kinds in sorted(self.census.items()):
            if kinds:
                parts = ", ".join(
                    f"{kind} x{c['count']} ({_fmt_bytes(c['bytes'])})"
                    for kind, c in sorted(kinds.items()))
            else:
                parts = "none"
            lines.append(f"[{prog}] collectives: {parts}")
            ov = self.overlap.get(prog)
            if ov and (ov["overlapped"]["count"] or ov["exposed"]["count"]):
                lines.append(
                    f"[{prog}] overlap: "
                    f"{ov['overlapped']['count']} overlapped "
                    f"({_fmt_bytes(ov['overlapped']['bytes'])}), "
                    f"{ov['exposed']['count']} exposed "
                    f"({_fmt_bytes(ov['exposed']['bytes'])})")
        for prog, mem in sorted(self.memory.items()):
            if not mem.get("peak_hbm_bytes"):
                continue
            bd = ", ".join(f"{c} {_fmt_bytes(b)}" for c, b in
                           mem.get("peak_breakdown", {}).items())
            lines.append(f"[{prog}] peak HBM (modeled): "
                         f"{_fmt_bytes(mem['peak_hbm_bytes'])}"
                         + (f" ({bd})" if bd else ""))
        for f in self.findings:
            lines.append(f"{f.severity.upper()} {f.key}: {f.message}")
        if self.suppressed:
            lines.append(f"({len(self.suppressed)} finding(s) suppressed by "
                         "baseline/config)")
        lines.append("lint: "
                     + ("OK" if self.ok else
                        f"{sum(1 for f in self.findings if f.severity == 'error')} error(s), "
                        f"{sum(1 for f in self.findings if f.severity == 'warning')} warning(s)"))
        return "\n".join(lines)


def load_baseline(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def save_baseline(report: Report, path: str):
    with open(path, "w") as f:
        json.dump(report.baseline_dict(), f, indent=2, sort_keys=True)
        f.write("\n")


def compare_census(got: Dict[str, Dict[str, int]],
                   want: Dict[str, Any],
                   program: str,
                   source: str) -> List[Finding]:
    """Exact census pin: any drift in collective counts — extra, missing, or
    changed — is an error. `want` values may be plain counts or
    {"count": n, "bytes": b} dicts (baseline form). A pin that carries
    bytes holds them too: XLA's combiner merges an added reduction into an
    existing op (one tuple-shaped all-reduce), which moves the bytes and
    leaves the count alone."""
    findings = []
    want_counts = {k: (v["count"] if isinstance(v, dict) else int(v))
                   for k, v in want.items()}
    want_bytes = {k: v["bytes"] for k, v in want.items()
                  if isinstance(v, dict) and "bytes" in v}
    for kind in sorted(set(want_counts) | set(got)):
        c = got.get(kind, {"count": 0, "bytes": 0})
        w, g = want_counts.get(kind, 0), c["count"]
        wb, gb = want_bytes.get(kind, c["bytes"]), c["bytes"]
        if w != g:
            what = (f"has {g} ({'extra' if g > w else 'missing'} "
                    f"{abs(g - w)})")
        elif wb != gb:
            what = (f"has the {g} op(s) moving {gb} bytes where {wb} are "
                    "pinned (combined into an existing op, the count hides "
                    "it)")
        else:
            continue
        more = (g, gb) > (w, wb)
        findings.append(Finding(
            rule="collective-census-drift",
            program=program,
            ident=kind,
            nbytes=gb,
            message=(f"{kind}: expected {w} per {source}, compiled program "
                     f"{what} — a collective was silently "
                     f"{'added' if more else 'removed'}"),
            data={"expected": w, "got": g, "expected_bytes": wb,
                  "got_bytes": gb, "source": source}))
    return findings


def _fmt_bytes(n: int) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if n < 1024 or unit == "GiB":
            return f"{n:.0f}{unit}" if unit == "B" else f"{n / 1:.1f}{unit}"
        n /= 1024
    return f"{n}B"
