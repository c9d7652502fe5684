"""Structured findings + the suppression registry.

A `Finding` is one fact the static pass proved about a compiled train
step: which rule fired, how bad it is, which target/entrypoint it lives
in, and the jaxpr provenance (the chain of enclosing sub-jaxprs — jit /
shard_map / scan / cond / remat — down to the offending equation).

Suppressions are the inline escape hatch: a module that does something
the linter flags ON PURPOSE registers a suppression NEXT TO the code
that causes it, with a mandatory reason string — so the analyzer doubles
as documentation of every deliberate deviation. A suppressed finding is
still reported (with its reason); it just stops counting against the
zero-high-severity gate.

This module is dependency-free (stdlib only) so engine modules can
import it at module scope without dragging jax tracing machinery in.
"""

from __future__ import annotations

import enum
import re
from dataclasses import dataclass, field
from fnmatch import fnmatch


class Severity(enum.IntEnum):
    """LOW = informational (always emitted, never gates); MEDIUM = smells
    that deserve a look; HIGH = provable TPU-cleanliness violations — the
    CI gate fails on any unsuppressed HIGH."""

    LOW = 0
    MEDIUM = 1
    HIGH = 2


@dataclass
class Finding:
    rule: str                    # registry name, e.g. "donation"
    severity: Severity
    target: str                  # probe name, e.g. "pipeline_lm:1f1b"
    site: str                    # entrypoint name, e.g. "_step"
    path: tuple = ()             # enclosing sub-jaxpr chain (prim names)
    message: str = ""
    suppressed: str | None = None  # reason string when suppressed
    # the registration that suppressed it (stale-suppression audit);
    # never serialized — `suppressed` carries the reason
    suppressed_by: object = field(default=None, repr=False,
                                  compare=False)

    @property
    def where(self) -> str:
        chain = "/".join(self.path)
        return f"{self.target}::{self.site}" + (f" [{chain}]" if chain
                                                else "")

    @property
    def key(self) -> str:
        """Stable identity for baseline diffing: location + message with
        the volatile dedup count (` (xN)`) stripped."""
        msg = re.sub(r" \(x\d+\)$", "", self.message)
        return "|".join((self.rule, self.target, self.site,
                         "/".join(self.path), msg))

    def to_dict(self) -> dict:
        """Machine-readable form (--format json). Stable fields: rule,
        severity (name), target, site, path (list), message,
        suppressed (reason or null), key."""
        return {
            "rule": self.rule,
            "severity": self.severity.name,
            "target": self.target,
            "site": self.site,
            "path": list(self.path),
            "message": self.message,
            "suppressed": self.suppressed,
            "key": self.key,
        }

    def format(self) -> str:
        tag = ("suppressed"
               if self.suppressed else self.severity.name)
        out = f"[{tag:>10}] {self.rule:<18} {self.where}: {self.message}"
        if self.suppressed:
            out += f"\n{'':>13}reason: {self.suppressed}"
        return out


@dataclass
class Suppression:
    rule: str       # rule name or "*"
    target: str     # fnmatch glob over the probe name
    match: str      # substring of the finding's message/site ("" = any)
    reason: str


_REGISTRY: list[Suppression] = []


def suppress(rule: str, target: str = "*", match: str = "",
             reason: str = "") -> Suppression:
    """Register an intentional-deviation suppression. `reason` is
    mandatory — the analyzer's report prints it, so the registration
    site IS the documentation of why the finding is deliberate."""
    assert reason.strip(), (
        "suppress() requires a non-empty reason string — the suppression "
        "doubles as documentation of the intentional finding")
    s = Suppression(rule, target, match, reason)
    _REGISTRY.append(s)
    return s


def registered_suppressions() -> tuple:
    return tuple(_REGISTRY)


def clear_suppressions(keep=()) -> None:
    """Testing hook: reset the registry (optionally to a saved snapshot
    from `registered_suppressions`)."""
    _REGISTRY.clear()
    _REGISTRY.extend(keep)


def apply_suppressions(findings: list) -> list:
    """Mark each finding suppressed by the first matching registration.
    Matching: rule name (or '*'), target glob, and `match` as a
    substring of `site`, the sub-jaxpr path, or the message."""
    for f in findings:
        for s in _REGISTRY:
            if s.rule not in ("*", f.rule):
                continue
            if not fnmatch(f.target, s.target):
                continue
            hay = " ".join((f.site, "/".join(f.path), f.message))
            if s.match and s.match not in hay:
                continue
            f.suppressed = s.reason
            f.suppressed_by = s
            break
    return findings


def stale_suppressions(results: dict, ran_rules=()) -> list:
    """The registry anti-rot audit: a registered suppression whose rule
    ran against a probe its target glob matches, yet matched NO finding,
    is itself a MEDIUM finding — the deviation it documented no longer
    exists and the registration must be deleted (or it will silently
    swallow a future regression). `results` is analyze()'s
    {probe: [Finding]} map AFTER apply_suppressions; `ran_rules` the
    rule names that actually ran (empty = audit every registration)."""
    used = {id(f.suppressed_by) for fs in results.values() for f in fs
            if f.suppressed_by is not None}
    out = []
    for s in _REGISTRY:
        if id(s) in used:
            continue
        if ran_rules and s.rule != "*" and s.rule not in ran_rules:
            continue  # its rule didn't run — nothing proven stale
        probes = [p for p in results if fnmatch(p, s.target)]
        if not probes:
            continue  # its target wasn't analyzed
        out.append(Finding(
            "stale-suppression", Severity.MEDIUM, probes[0],
            "(suppression registry)", (),
            f"suppression (rule={s.rule!r}, target={s.target!r}, "
            f"match={s.match!r}) matched no finding in this run — the "
            f"deviation it documented is gone; delete the registration "
            f"before it swallows a future regression (its reason was: "
            f"{s.reason})"))
    return out


def gate_count(findings: list) -> int:
    """Number of findings that fail the CI gate: HIGH and unsuppressed."""
    return sum(1 for f in findings
               if f.severity == Severity.HIGH and not f.suppressed)
