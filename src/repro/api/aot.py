"""AotExecutableCache — persistent ahead-of-time compiled decode cache.

The serving cold path costs ~1.1 s: tracing the policy step, lowering and
XLA-compiling the bucket-shaped greedy decode.  A warm process pays it once
per bucket shape (the jit cache); every *fresh* process pays it again.
This cache moves the bound from once-per-process to once-per-build:

* after a :class:`~repro.api.PlacementService` traces a bucket shape, the
  engine's lowered executable is serialized (``jax.export``) and written
  under ``<dir>/<spec_hash>/greedy_<format>_<device>_<v>v<e>e<g>g.jaxaot``;
* a fresh process serving the same ``(spec_hash, bucket shape,
  batch_slots)`` loads the blob and decodes through the deserialized
  executable — **zero traces** (``DynamicRolloutEngine.shape_keys_seen``
  stays empty; hits are counted in :attr:`AotExecutableCache.hits` and the
  engine's ``aot_hits``).

Keying and invalidation:

* ``spec_hash`` (the :meth:`~repro.api.PlacementSpec.spec_hash` of the
  policy's run document) names the policy architecture + config — two
  tenants never share executables.  Parameter *values* are call-time
  operands, so fine-tuning the policy does **not** invalidate its cache.
* the padded bucket shape ``(v, e)`` and decode width ``g`` pin the operand
  shapes — exactly what the jit cache would key on.
* ``device`` is the platform and device kind the cache serves
  (``tpu-TPU-v5-lite``, ``cpu-cpu``): ``jax.export`` lowers for the backend
  it runs on, so a directory written on one platform is a miss, never a
  failing decode, on another.
* blobs embed jax's own export calling-convention version; a jax upgrade
  that cannot replay a blob surfaces as a load failure, which callers
  treat as a miss (re-trace, re-store).  ``clear(spec_hash)`` drops a
  tenant's entries wholesale.

Writes are atomic (temp file + ``os.replace``), so concurrent servers
racing on one directory at worst redo an export, never read a torn blob.
"""
from __future__ import annotations

import os
import re
import tempfile
from typing import Dict, List, Optional, Tuple

import jax

__all__ = ["AotExecutableCache"]

_FORMAT = "v1"  # bump to orphan old blobs if the on-disk layout changes


def _device_key() -> str:
    """``<platform>-<device kind>`` of this process's first device, made
    safe for a file name (``TPU v5 lite`` → ``TPU-v5-lite``)."""
    dev = jax.devices()[0]
    return re.sub(r"[^A-Za-z0-9.]+", "-", f"{dev.platform}-{dev.device_kind}")


class AotExecutableCache:
    """See module docstring.  Example::

        cache = AotExecutableCache("ckpt/aot")
        service = PlacementService(session, aot_cache=cache)
        # ... serve ...; a later process with the same cache dir performs
        # zero recompiles for every bucket shape served here.
    """

    def __init__(self, directory: str):
        self.directory = str(directory)
        os.makedirs(self.directory, exist_ok=True)
        #: platform + device kind the blobs are keyed by: this process's
        #: first device.
        self.device_key = _device_key()
        self.hits = 0
        self.misses = 0
        self.stores = 0
        self.load_failures = 0

    # ------------------------------------------------------------ key layout
    def _path(self, spec_hash: str, bucket_shape: Tuple[int, int],
              batch_slots: int) -> str:
        v, e = (int(x) for x in bucket_shape)
        fname = (f"greedy_{_FORMAT}_{self.device_key}_"
                 f"{v}v{e}e{int(batch_slots)}g.jaxaot")
        return os.path.join(self.directory, str(spec_hash), fname)

    # -------------------------------------------------------------- load/store
    def load(self, spec_hash: str, bucket_shape: Tuple[int, int],
             batch_slots: int) -> Optional[bytes]:
        """→ the serialized executable, or ``None`` (counted as a miss).

        An unreadable blob (torn write survivor, jax version skew) counts
        as both a miss and a ``load_failure`` — the caller re-traces and
        overwrites it.
        """
        path = self._path(spec_hash, bucket_shape, batch_slots)
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError:
            self.misses += 1
            return None
        if not blob:
            self.misses += 1
            self.load_failures += 1
            return None
        self.hits += 1
        return blob

    def store(self, spec_hash: str, bucket_shape: Tuple[int, int],
              batch_slots: int, blob: bytes) -> str:
        """Atomically persist ``blob``; → the written path."""
        path = self._path(spec_hash, bucket_shape, batch_slots)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path),
                                   suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        self.stores += 1
        return path

    def note_load_failure(self) -> None:
        """Record that a loaded blob failed to deserialize downstream."""
        self.load_failures += 1

    # --------------------------------------------------------------- queries
    def entries(self, spec_hash: Optional[str] = None) -> List[str]:
        """Relative paths of every persisted executable (one tenant's with
        ``spec_hash``)."""
        roots = [spec_hash] if spec_hash is not None else sorted(
            d for d in os.listdir(self.directory)
            if os.path.isdir(os.path.join(self.directory, d)))
        out: List[str] = []
        for root in roots:
            tenant_dir = os.path.join(self.directory, root)
            if not os.path.isdir(tenant_dir):
                continue
            out.extend(os.path.join(root, f)
                       for f in sorted(os.listdir(tenant_dir))
                       if f.endswith(".jaxaot"))
        return out

    def clear(self, spec_hash: str) -> int:
        """Drop one tenant's executables; → number removed."""
        removed = 0
        tenant_dir = os.path.join(self.directory, str(spec_hash))
        if not os.path.isdir(tenant_dir):
            return 0
        for f in os.listdir(tenant_dir):
            if f.endswith(".jaxaot"):
                os.unlink(os.path.join(tenant_dir, f))
                removed += 1
        return removed

    def stats(self) -> Dict[str, int]:
        return {"aot_hits": self.hits, "aot_misses": self.misses,
                "aot_stores": self.stores,
                "aot_load_failures": self.load_failures,
                "aot_entries": len(self.entries())}
