"""Waiver file support.

A waiver records a *reviewed* exception to a rule — every entry carries
the one-line justification, so suppressions are auditable in one place
instead of scattered inline.  Format (``analysis/waivers.toml``)::

    [[waiver]]
    rule   = "FL101"
    match  = "Channel._rows@Channel.__len__"
    reason = "GIL-atomic int read on the hot path; staleness is fine"
    file   = "src/repro/core/engine.py"   # optional narrowing

``match`` is a substring of the finding's symbol or message (symbols are
stable across line drift, so prefer them).  A waiver that matches no
finding is itself reported (FL901) — stale waivers rot into blanket
suppressions otherwise.
"""
from __future__ import annotations

import os
import tomllib
from dataclasses import dataclass
from typing import Iterable, List, Optional, Tuple

from .findings import Finding

#: default lookup locations, first hit wins
DEFAULT_WAIVER_PATHS = ("analysis/waivers.toml",
                        "src/repro/analysis/waivers.toml")


@dataclass(frozen=True)
class Waiver:
    rule: str
    match: str
    reason: str
    file: str = ""

    def covers(self, f: Finding) -> bool:
        if self.rule and f.rule != self.rule:
            return False
        if self.file and not f.file.replace(os.sep, "/").endswith(self.file):
            return False
        return self.match in f.symbol or self.match in f.message


class WaiverError(ValueError):
    pass


def load_waivers(path: str) -> List[Waiver]:
    with open(path, "rb") as fh:
        data = tomllib.load(fh)
    out: List[Waiver] = []
    for i, entry in enumerate(data.get("waiver", [])):
        if not isinstance(entry, dict):
            raise WaiverError(f"{path}: waiver #{i + 1} is not a table")
        missing = [k for k in ("rule", "match", "reason") if not entry.get(k)]
        if missing:
            raise WaiverError(
                f"{path}: waiver #{i + 1} is missing {missing} "
                "(every waiver needs rule, match and a justification)")
        out.append(Waiver(rule=str(entry["rule"]),
                          match=str(entry["match"]),
                          reason=str(entry["reason"]),
                          file=str(entry.get("file", ""))))
    return out


def find_waiver_file(explicit: Optional[str] = None) -> Optional[str]:
    if explicit:
        return None if explicit == "none" else explicit
    for cand in DEFAULT_WAIVER_PATHS:
        if os.path.isfile(cand):
            return cand
    return None


def apply_waivers(findings: Iterable[Finding], waivers: List[Waiver]
                  ) -> Tuple[List[Finding], List[Tuple[Finding, Waiver]]]:
    """Split findings into (kept, waived) and append FL901 for stale
    waivers.  Kept includes the FL901 notes."""
    kept: List[Finding] = []
    waived: List[Tuple[Finding, Waiver]] = []
    used = [False] * len(waivers)
    for f in findings:
        hit = None
        for i, w in enumerate(waivers):
            if w.covers(f):
                used[i] = True
                hit = w
                break
        if hit is None:
            kept.append(f)
        else:
            waived.append((f, hit))
    for w, u in zip(waivers, used):
        if not u:
            kept.append(Finding(
                "FL901", "note", "analysis/waivers.toml", 0,
                f"waiver for {w.rule} matched no finding (match="
                f"{w.match!r}) — remove it or fix the pattern",
                symbol=w.match))
    return kept, waived
