"""Persistent autotune cache — one JSON file per device kind.

The reference BigDL's Engine picked shape-tuned MKL primitives at runtime
on every process start (spark/dl/.../Engine.scala convolution-algorithm
selection); re-measuring per process is wasteful on TPU where one candidate
sweep costs whole compile cycles. So decisions
persist: ``~/.cache/bigdl_tpu/autotune/<device-kind>.json`` (override the
directory with ``BIGDL_TPU_AUTOTUNE_CACHE``), versioned so a format change
can never misread old decisions as current ones.

Determinism contract (ISSUE 1 acceptance): the serialized bytes are a pure
function of the entries — keys sorted, no timestamps, no environment
fingerprints — so two ``measure`` runs over identical keys on the same
device produce byte-identical files (dry mode) or files differing only in
measured milliseconds (chip mode). Corrupt or version-mismatched files
load as empty (the tuner then falls back to defaults) instead of raising:
a half-written cache after a killed run must never take down a training
run.

Namespaces in one file (the key's leading ``op`` token): ``flash`` /
``bn_stats`` / ``bn_fba`` / ``conv_layouts`` (global per-variant triple)
and, from round 8, ``conv_geom`` — per-conv-geometry layout decisions
keyed by (kh, kw, stride, cin, cout, groups, dilation, dtype, pass),
written by measure mode or imported from probe output with source
``"probe"`` (tuning.put_geom_decisions). Entry sources: ``measured`` /
``dry`` / ``probe``.
"""

from __future__ import annotations

import json
import os
import tempfile
from typing import Dict, Optional

__all__ = ["AutotuneCache", "CACHE_VERSION", "cache_dir", "cache_path",
           "device_kind", "device_slug"]

CACHE_VERSION = 1


def cache_dir() -> str:
    """Resolve the cache directory: BIGDL_TPU_AUTOTUNE_CACHE wins (tests,
    shared-filesystem clusters); default is a per-user path."""
    explicit = os.environ.get("BIGDL_TPU_AUTOTUNE_CACHE")
    if explicit:
        return explicit
    return os.path.join(os.path.expanduser("~"), ".cache", "bigdl_tpu",
                        "autotune")


def device_kind() -> str:
    """The ambient accelerator kind ("TPU v5 lite", ...); "cpu" when no
    backend resolves (e.g. jax not initialized yet in a dry test)."""
    try:
        import jax
        return getattr(jax.devices()[0], "device_kind", "cpu") or "cpu"
    except Exception:
        return "cpu"


def device_slug(kind: str) -> str:
    """Filesystem-safe spelling of a device kind ("TPU v5 lite" ->
    "tpu-v5-lite")."""
    slug = "".join(c if c.isalnum() else "-" for c in kind.lower())
    while "--" in slug:
        slug = slug.replace("--", "-")
    return slug.strip("-") or "unknown"


def cache_path(kind: Optional[str] = None) -> str:
    return os.path.join(cache_dir(),
                        device_slug(kind or device_kind()) + ".json")


class AutotuneCache:
    """In-memory view over one device kind's JSON decision file.

    ``get``/``put`` operate on the in-memory layer; ``save()`` writes the
    whole store atomically (temp file + rename) so readers never see a
    torn file. Loading tolerates every corruption mode by falling back to
    an empty store — decisions are an optimization, never a dependency.
    """

    def __init__(self, kind: Optional[str] = None,
                 path: Optional[str] = None):
        self.kind = kind or device_kind()
        self.path = path or cache_path(self.kind)
        self.entries: Dict[str, dict] = {}
        self._load()

    def _load(self) -> None:
        try:
            with open(self.path) as f:
                blob = json.load(f)
        except (OSError, ValueError):
            return  # missing or corrupt: start empty
        if not isinstance(blob, dict) or blob.get("version") != CACHE_VERSION:
            return  # version mismatch: stale decisions are not decisions
        entries = blob.get("entries")
        if isinstance(entries, dict):
            self.entries = {str(k): dict(v) for k, v in entries.items()
                            if isinstance(v, dict) and "config" in v}

    def get(self, key: str) -> Optional[dict]:
        ent = self.entries.get(key)
        return dict(ent) if ent is not None else None

    def put(self, key: str, entry: dict) -> None:
        self.entries[key] = dict(entry)

    def save(self) -> None:
        """Atomic, deterministic write: sorted keys, fixed separators, no
        wall-clock anywhere in the payload."""
        blob = {"version": CACHE_VERSION, "device_kind": self.kind,
                "entries": dict(sorted(self.entries.items()))}
        payload = json.dumps(blob, sort_keys=True, indent=1) + "\n"
        d = os.path.dirname(self.path) or "."
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=d, prefix=".autotune_")
        try:
            with os.fdopen(fd, "w") as f:
                f.write(payload)
            os.replace(tmp, self.path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
