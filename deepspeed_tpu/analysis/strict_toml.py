"""Strict TOML loading shared by the declarative CI gates.

``analysis/budgets.py`` (HLO ceilings) and ``analysis/concurrency.py``
(lockdep waivers) enforce the same file discipline: a config entry that
silently does nothing is worse than no entry, so

* **unknown keys are hard errors** — a typo'd key must fail the gate,
  not become a budget/waiver that never fires;
* **vacuous entries are hard errors** — an entry missing the fields
  that make it bite (a budget whose pass never ran, a waiver with no
  key or no justification) is rejected at load time.

Both gates route their validation through this module so the two
checkers cannot drift.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable

__all__ = ["StrictTomlError", "load_toml", "check_keys", "require"]


class StrictTomlError(ValueError):
    """Malformed strict-TOML config (unknown key, bad type, vacuous
    entry, missing table)."""


def load_toml(path: str) -> Dict[str, Any]:
    """Parse ``path`` as TOML; parse failures carry the file name."""
    import tomllib

    try:
        with open(path, "rb") as f:
            return tomllib.load(f)
    except tomllib.TOMLDecodeError as e:
        raise StrictTomlError(f"{path}: invalid TOML: {e}") from e


def check_keys(table: Dict[str, Any], allowed: Iterable[str],
               where: str, error: type = StrictTomlError) -> None:
    """Hard-error on any key of ``table`` outside ``allowed``."""
    allowed = set(allowed)
    unknown = set(table) - allowed
    if unknown:
        raise error(
            f"{where}: unknown key(s) {sorted(unknown)}; "
            f"known keys: {sorted(allowed)}")


def require(cond: bool, message: str,
            error: type = StrictTomlError) -> None:
    """Hard-error unless ``cond`` — the anti-vacuous assert both gates
    use for 'this entry must actually bite'."""
    if not cond:
        raise error(message)
