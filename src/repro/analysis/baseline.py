"""Baseline suppressions: `analysis_baseline.toml` at the repo root.

A suppression is a JUSTIFIED, reviewed exception — every entry must
carry a non-empty `reason`, and matches are as narrow as the entry
makes them:

    [[suppress]]
    rule   = "J001"                         # required: exact rule id
    path   = "src/repro/data/synthetic.py"  # required: repo-relative path
    symbol = "blob_ring"                    # optional: enclosing qualname
    reason = "same key reused on purpose: the blob and the ring must "
             "share one draw of radii"

Omitting `symbol` suppresses the rule for the whole file (use
sparingly). Line numbers are deliberately NOT part of the match — they
churn on every edit; rule+path+symbol is stable across refactors that
do not change behavior.

A malformed baseline (missing reason, unknown rule id) is itself a
fatal error: the suppression file must never rot into a silent
allowlist.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Dict, List, Tuple

try:
    import tomllib
except ImportError:                       # Python 3.10: stdlib tomllib is 3.11+
    import tomli as tomllib

from repro.analysis.findings import RULES, Finding


@dataclasses.dataclass(frozen=True)
class Suppression:
    rule: str
    path: str
    symbol: str          # "" = whole file
    reason: str

    def matches(self, f: Finding) -> bool:
        if self.rule != f.rule or self.path != f.path:
            return False
        return self.symbol in ("", f.symbol)


class BaselineError(ValueError):
    """analysis_baseline.toml is malformed; fix the file, don't skip it."""


def load_baseline(path: str | Path) -> List[Suppression]:
    """Parse + validate the baseline file; missing file = no suppressions."""
    p = Path(path)
    if not p.exists():
        return []
    with open(p, "rb") as fh:
        doc = tomllib.load(fh)
    entries = doc.get("suppress", [])
    if not isinstance(entries, list):
        raise BaselineError(f"{p}: [[suppress]] must be an array of tables")
    out = []
    for i, e in enumerate(entries):
        where = f"{p}: suppress[{i}]"
        for req in ("rule", "path", "reason"):
            if not isinstance(e.get(req), str) or not e.get(req).strip():
                raise BaselineError(f"{where}: non-empty {req!r} is required")
        if e["rule"] not in RULES:
            raise BaselineError(f"{where}: unknown rule id {e['rule']!r}; "
                                f"known: {sorted(RULES)}")
        out.append(Suppression(rule=e["rule"],
                               path=Path(e["path"]).as_posix(),
                               symbol=str(e.get("symbol", "")),
                               reason=e["reason"].strip()))
    return out


def apply_baseline(findings: List[Finding],
                   suppressions: List[Suppression]
                   ) -> Tuple[List[Finding], List[Finding], List[Suppression]]:
    """Partition findings into (active, suppressed); third element is the
    stale suppressions that matched nothing (reported so the baseline
    shrinks when fixes land, instead of accreting dead entries)."""
    active: List[Finding] = []
    suppressed: List[Finding] = []
    used: Dict[Suppression, int] = {s: 0 for s in suppressions}
    for f in findings:
        hit = next((s for s in suppressions if s.matches(f)), None)
        if hit is None:
            active.append(f)
        else:
            used[hit] += 1
            suppressed.append(f)
    stale = [s for s, n in used.items() if n == 0]
    return active, suppressed, stale
