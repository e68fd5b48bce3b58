"""Workload traces: the schema of a replayable trace, and its capture.

This module records traffic; it replays nothing.  The load generator that
replays a trace on the chip, timing each request from when it was *due*, is
``benchmark/loadgen.py``.

* **schema** — :class:`WorkloadRequest` and :func:`save_workload` /
  :func:`load_workload`.  The file is JSONL: a header record
  ``{"kind": "dstpu-workload", "version": 1, "meta": {...}}``, then one
  record a request (arrival offset, prompt token list, generation budget,
  stop tokens, deadline, cancel, temperature, tenant, SLO class, adapter).
  An unknown key is a hard error.  ``python -m deepspeed_tpu.observability
  workload <file>`` renders a summary.
* **capture** — :class:`WorkloadCapture` records every ``broker.submit`` /
  ``cancel`` of this process into that schema.  The broker calls the
  module-level :func:`note_submit` / :func:`note_cancel` hooks, which are
  no-ops unless a capture is installed.

Nothing here imports the serving stack: the broker imports this module for
the capture hooks.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..utils.locks import named_lock

__all__ = [
    "WorkloadCapture",
    "WorkloadError",
    "WorkloadRequest",
    "load_workload",
    "note_cancel",
    "note_submit",
    "save_workload",
]

WORKLOAD_KIND = "dstpu-workload"
WORKLOAD_VERSION = 1

_RECORD_KEYS = {
    "offset_s", "prompt", "max_new_tokens", "stop_token_ids",
    "deadline_s", "cancel_after_s", "rid", "template",
    "temperature", "tenant", "slo_class", "adapter",
}


class WorkloadError(ValueError):
    """Malformed workload file (bad header, unknown key, bad record)."""


@dataclasses.dataclass
class WorkloadRequest:
    """One request of a workload trace.  ``offset_s`` is the arrival time
    relative to the first request; ``template`` (a generated trace only)
    records which prompt template the prefix came from — the prefix-sharing
    structure a prefix-cache experiment wants to preserve."""

    offset_s: float
    prompt: List[int]
    max_new_tokens: Optional[int] = None
    stop_token_ids: Tuple[int, ...] = ()
    deadline_s: Optional[float] = None
    cancel_after_s: Optional[float] = None
    rid: Optional[str] = None
    template: Optional[int] = None
    temperature: Optional[float] = None
    tenant: Optional[str] = None
    slo_class: Optional[str] = None
    adapter: Optional[str] = None

    def to_record(self) -> Dict[str, Any]:
        rec: Dict[str, Any] = {"offset_s": round(self.offset_s, 6),
                               "prompt": list(self.prompt)}
        if self.max_new_tokens is not None:
            rec["max_new_tokens"] = int(self.max_new_tokens)
        if self.stop_token_ids:
            rec["stop_token_ids"] = [int(t) for t in self.stop_token_ids]
        if self.deadline_s is not None:
            rec["deadline_s"] = float(self.deadline_s)
        if self.cancel_after_s is not None:
            rec["cancel_after_s"] = round(float(self.cancel_after_s), 6)
        if self.rid is not None:
            rec["rid"] = self.rid
        if self.template is not None:
            rec["template"] = int(self.template)
        if self.temperature is not None:
            rec["temperature"] = float(self.temperature)
        if self.tenant is not None:
            rec["tenant"] = self.tenant
        if self.slo_class is not None:
            rec["slo_class"] = self.slo_class
        if self.adapter is not None:
            rec["adapter"] = self.adapter
        return rec

    @classmethod
    def from_record(cls, rec: Dict[str, Any], lineno: int
                    ) -> "WorkloadRequest":
        unknown = set(rec) - _RECORD_KEYS
        if unknown:
            raise WorkloadError(
                f"line {lineno}: unknown workload record key(s) "
                f"{sorted(unknown)}; known keys: {sorted(_RECORD_KEYS)}")
        if "offset_s" not in rec or "prompt" not in rec:
            raise WorkloadError(
                f"line {lineno}: workload record needs offset_s and prompt")
        prompt = rec["prompt"]
        if not isinstance(prompt, list) or not prompt or not all(
                isinstance(t, int) and not isinstance(t, bool)
                for t in prompt):
            raise WorkloadError(
                f"line {lineno}: prompt must be a non-empty token id list")
        return cls(
            offset_s=float(rec["offset_s"]), prompt=[int(t) for t in prompt],
            max_new_tokens=rec.get("max_new_tokens"),
            stop_token_ids=tuple(rec.get("stop_token_ids", ())),
            deadline_s=rec.get("deadline_s"),
            cancel_after_s=rec.get("cancel_after_s"),
            rid=rec.get("rid"), template=rec.get("template"),
            temperature=rec.get("temperature"), tenant=rec.get("tenant"),
            slo_class=rec.get("slo_class"), adapter=rec.get("adapter"))


# ---------------------------------------------------------------------------
# save / load (canonical JSONL schema)
# ---------------------------------------------------------------------------


def save_workload(path: str, requests: Sequence[WorkloadRequest],
                  meta: Optional[Dict[str, Any]] = None) -> str:
    """Write the canonical JSONL: header record, then one per request."""
    header = {"kind": WORKLOAD_KIND, "version": WORKLOAD_VERSION,
              "meta": dict(meta or {})}
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        f.write(json.dumps(header, separators=(",", ":")) + "\n")
        for r in requests:
            f.write(json.dumps(r.to_record(), separators=(",", ":")) + "\n")
    os.replace(tmp, path)
    return path


def load_workload(path: str
                  ) -> Tuple[Dict[str, Any], List[WorkloadRequest]]:
    """Read and validate a workload file; returns ``(meta, requests)``
    sorted by arrival offset.  Hard-errors on schema violations — a
    silently-misread workload would measure the wrong traffic."""
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines() if ln.strip()]
    if not lines:
        raise WorkloadError(f"{path}: empty workload file")
    try:
        header = json.loads(lines[0])
    except json.JSONDecodeError as e:
        raise WorkloadError(f"{path}: header is not JSON: {e}")
    if not isinstance(header, dict) or header.get("kind") != WORKLOAD_KIND:
        raise WorkloadError(
            f"{path}: not a workload trace (want header kind="
            f"{WORKLOAD_KIND!r}, got {header!r})")
    if header.get("version") != WORKLOAD_VERSION:
        raise WorkloadError(
            f"{path}: workload version {header.get('version')!r} != "
            f"{WORKLOAD_VERSION}")
    requests: List[WorkloadRequest] = []
    for lineno, ln in enumerate(lines[1:], 2):
        try:
            rec = json.loads(ln)
        except json.JSONDecodeError as e:
            raise WorkloadError(f"{path}: line {lineno}: not JSON: {e}")
        requests.append(WorkloadRequest.from_record(rec, lineno))
    requests.sort(key=lambda r: r.offset_s)
    return dict(header.get("meta") or {}), requests


# ---------------------------------------------------------------------------
# capture at the broker
# ---------------------------------------------------------------------------

_capture_lock = named_lock("replay.capture_install")
_capture: Optional["WorkloadCapture"] = None


class WorkloadCapture:
    """Records live broker traffic into the workload schema.  Use as a
    context manager; while installed, every ``RequestBroker.submit`` /
    ``cancel`` in this process lands here via the module hooks::

        with WorkloadCapture() as cap:
            ... serve traffic ...
        save_workload(path, cap.to_workload(), cap.meta())
    """

    def __init__(self) -> None:
        self._lock = named_lock("replay.capture")
        self._t0: Optional[float] = None
        self._by_rid: Dict[str, Dict[str, Any]] = {}
        self._order: List[str] = []

    # hook targets — must never raise (they ride the submit path)

    def _note_submit(self, rid: str, t: float, prompt: Sequence[int],
                     max_new_tokens: Optional[int],
                     stop_token_ids: Sequence[int],
                     deadline_s: Optional[float],
                     temperature: Optional[float] = None,
                     tenant: Optional[str] = None,
                     slo_class: Optional[str] = None,
                     adapter: Optional[str] = None) -> None:
        with self._lock:
            if rid in self._by_rid:
                return  # failover resubmit of a captured request
            if self._t0 is None:
                self._t0 = t
            self._by_rid[rid] = {
                "offset_s": t - self._t0, "t": t,
                "prompt": [int(x) for x in prompt],
                "max_new_tokens": max_new_tokens,
                "stop_token_ids": tuple(int(x) for x in stop_token_ids),
                "deadline_s": deadline_s, "cancel_after_s": None,
                "temperature": temperature, "tenant": tenant,
                "slo_class": slo_class, "adapter": adapter,
            }
            self._order.append(rid)

    def _note_cancel(self, rid: str, t: float) -> None:
        with self._lock:
            rec = self._by_rid.get(rid)
            if rec is not None and rec["cancel_after_s"] is None:
                rec["cancel_after_s"] = max(0.0, t - rec["t"])

    # reading

    def __len__(self) -> int:
        with self._lock:
            return len(self._order)

    def to_workload(self) -> List[WorkloadRequest]:
        with self._lock:
            return [WorkloadRequest(
                offset_s=rec["offset_s"], prompt=list(rec["prompt"]),
                max_new_tokens=rec["max_new_tokens"],
                stop_token_ids=rec["stop_token_ids"],
                deadline_s=rec["deadline_s"],
                cancel_after_s=rec["cancel_after_s"], rid=rid,
                temperature=rec["temperature"], tenant=rec["tenant"],
                slo_class=rec["slo_class"], adapter=rec["adapter"])
                for rid in self._order
                for rec in (self._by_rid[rid],)]

    def meta(self) -> Dict[str, Any]:
        with self._lock:
            return {"source": "capture", "requests": len(self._order),
                    "captured_wall": time.time()}

    # installation

    def __enter__(self) -> "WorkloadCapture":
        global _capture
        with _capture_lock:
            if _capture is not None:
                raise RuntimeError("a WorkloadCapture is already installed")
            _capture = self
        return self

    def __exit__(self, *exc) -> None:
        global _capture
        with _capture_lock:
            if _capture is self:
                _capture = None


def note_submit(rid: str, t: float, prompt: Sequence[int],
                max_new_tokens: Optional[int],
                stop_token_ids: Sequence[int],
                deadline_s: Optional[float],
                temperature: Optional[float] = None,
                tenant: Optional[str] = None,
                slo_class: Optional[str] = None,
                adapter: Optional[str] = None) -> None:
    """Broker hook: record a submit into the installed capture (no-op —
    one dict lookup — when no capture is running)."""
    cap = _capture
    if cap is not None:
        try:
            cap._note_submit(rid, t, prompt, max_new_tokens,
                             stop_token_ids, deadline_s,
                             temperature=temperature, tenant=tenant,
                             slo_class=slo_class, adapter=adapter)
        except Exception:  # noqa: BLE001 — must never break the submit path
            pass


def note_cancel(rid: str, t: float) -> None:
    """Broker hook: record a cancel against a captured submit."""
    cap = _capture
    if cap is not None:
        try:
            cap._note_cancel(rid, t)
        except Exception:  # noqa: BLE001
            pass
