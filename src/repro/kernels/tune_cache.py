"""Persistent autotuning cache — the memoized output of the parameter search.

The paper's code generator amortizes its search by emitting one kernel per
shape class and reusing it for every GEMM in that class; our analogue is a
small JSON file mapping

    {device_kind}/{shape_class}/b{in_bytes}/ft_{ft_level}  →  (bm, bn, bk)

so the (enumerate → score/measure) pass in `kernels.search` runs once per
class per device and every later `autotune.best_params()` call is a dict
lookup. Which file backs the process-wide cache (`default_cache`):

  * ``$REPRO_TUNE_CACHE`` when set — a writable file (tests, tuning runs);
  * otherwise the committed ``benchmarks/tuned/<device_kind>.json`` of the
    checkout, read-only: a miss is scored by the roofline model and kept in
    memory, never written. So the tiles a run compiles depend only on the
    committed files and the code, not on a developer's home directory.

Robustness: a missing, corrupt, or foreign-schema file degrades to an empty
cache (never an exception on the hot path); writes are atomic
(tmp + ``os.replace``) so a crashed process cannot truncate the cache.
"""
from __future__ import annotations

import dataclasses
import json
import os
import tempfile
from typing import Dict, Optional, Tuple

from .autotune import KernelParams, device_kind

_SCHEMA = 1
_ENV_VAR = "REPRO_TUNE_CACHE"


#: Committed tuning files, one per device kind (`benchmarks/tune_campaign`).
TUNED_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         os.pardir, os.pardir, os.pardir, "benchmarks",
                         "tuned")


def default_path() -> str:
    env = os.environ.get(_ENV_VAR)
    if env:
        return env
    return os.path.normpath(os.path.join(TUNED_DIR, f"{device_kind()}.json"))


def cache_key(device_kind: str, shape_class: str, in_bytes: int,
              ft_level: str, caps: Optional[Tuple[int, int, int]] = None,
              variant: str = "", batch: str = "") -> str:
    """`caps` is the search-space ceiling (per-dim max candidate tile) the
    triggering shape imposed. It must be part of the key: without it, a
    small shape that misses first would pin its capped winner onto every
    later same-class shape whose search space is wider (order-dependent
    tuning).

    `variant` is the kernel-template variant (`KernelSpec.variant_key()` —
    fused epilogue chain + non-default dtypes + batched/grouped body, and
    since PR 5 the flash-attention family: ``flashfwd[_stats]`` /
    ``flashbwd_dq`` / ``flashbwd_dkv``, whose (bm, bn) are the stationary/
    streamed sequence blocks). Fused epilogues change the VMEM budget and
    the roofline intensity, so two variants of one class may tune to
    different tiles; the plain variant keeps the empty string so PR-1
    cache files stay valid.

    `batch` is the batch/group-count component of a batched launch —
    ``"b_<n>"`` (uniform batch count) or ``"g_<n>"`` (ragged group count),
    power-of-two bucketed by `autotune.best_params`. The count shifts the
    roofline (batch multiplies every traffic/FLOP term; groups add
    per-group row padding that grows with bm), so it is part of the key;
    2-D launches keep the empty string and existing keys stay valid."""
    dev = device_kind.strip().lower().replace(" ", "_")
    cap = "" if caps is None else f"/c{caps[0]}x{caps[1]}x{caps[2]}"
    var = f"/v_{variant}" if variant else ""
    bat = f"/{batch}" if batch else ""
    return f"{dev}/{shape_class}{cap}/b{in_bytes}/ft_{ft_level}{var}{bat}"


class TuneCache:
    """Dict-like view over the JSON tuning file. Entries are
    ``key → [bm, bn, bk, shape_class]``. A ``writable=False`` cache keeps
    its `put`s in memory and never touches the file."""

    def __init__(self, path: Optional[str] = None, writable: bool = True):
        self.path = path or default_path()
        self.writable = writable
        self._entries: Dict[str, Tuple[int, int, int, str]] = {}
        self._loaded = False

    # -- persistence -------------------------------------------------------

    def load(self) -> "TuneCache":
        self._entries = {}
        self._loaded = True
        try:
            with open(self.path, "r", encoding="utf-8") as f:
                raw = json.load(f)
            if raw.get("schema") != _SCHEMA:
                return self
            for key, val in raw.get("entries", {}).items():
                bm, bn, bk, cls = val
                self._entries[str(key)] = (int(bm), int(bn), int(bk), str(cls))
        except (OSError, ValueError, TypeError, KeyError):
            self._entries = {}
        return self

    def save(self) -> None:
        payload = {"schema": _SCHEMA,
                   "entries": {k: list(v) for k, v in self._entries.items()}}
        tmp = None
        try:
            d = os.path.dirname(os.path.abspath(self.path))
            os.makedirs(d, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
            with os.fdopen(fd, "w", encoding="utf-8") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        except OSError:
            # An unwritable cache must never break the GEMM hot path — the
            # search result is still returned, just not persisted.
            if tmp is not None:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass

    # -- access ------------------------------------------------------------

    def _ensure(self) -> None:
        if not self._loaded:
            self.load()

    def get(self, key: str) -> Optional[KernelParams]:
        self._ensure()
        hit = self._entries.get(key)
        if hit is None:
            return None
        bm, bn, bk, cls = hit
        return KernelParams(bm=bm, bn=bn, bk=bk, shape_class=cls)

    def put(self, key: str, params: KernelParams, persist: bool = True) -> None:
        self._ensure()
        self._entries[key] = (params.bm, params.bn, params.bk,
                              params.shape_class)
        if persist and self.writable:
            self.save()

    def __len__(self) -> int:
        self._ensure()
        return len(self._entries)

    def keys(self):
        self._ensure()
        return list(self._entries)

    def as_dict(self) -> Dict[str, Tuple[int, int, int, str]]:
        self._ensure()
        return dict(self._entries)


_DEFAULT: Optional[TuneCache] = None


def default_cache() -> TuneCache:
    """Process-wide cache singleton (re-pointed by `reset`, e.g. after the
    ``REPRO_TUNE_CACHE`` env var changes in tests). Writable only when that
    variable names the file."""
    global _DEFAULT
    if _DEFAULT is None or _DEFAULT.path != default_path():
        _DEFAULT = TuneCache(writable=bool(os.environ.get(_ENV_VAR)))
    return _DEFAULT


def reset() -> None:
    global _DEFAULT
    _DEFAULT = None
