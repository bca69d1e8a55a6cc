"""Model-weight sharing across same-node replicas via the shm plane.

Every LLM replica on a node needs the same parameter pytree.  Loading it
per replica costs init time and N× host memory; instead the first
replica to arrive publishes the flattened parameters into ONE /dev/shm
segment (the same plane the KV pool and object store use) and later
replicas attach read-only — ``np.frombuffer`` views over the shared
mmap, zero-copy on the host side (``jnp.asarray`` copies onto device;
on the CPU rig that copy IS the only copy).

Publication protocol (crash-safe, single-writer):

- segment ``rtpu_llmw_<key>.<publisher_pid>`` holds header (json: leaf
  shapes/dtypes/offsets) + raw leaf bytes; the pid in the name makes a
  SIGKILLed publisher's segment recognizably orphaned, the same
  discipline the export spools use (``kv_cache.py``);
- writers race on an O_EXCL ``.lock`` sentinel; the loser polls for a
  live publisher's ``.ready`` sentinel.  A writer that dies mid-publish
  leaves no ``.ready``; a stale lock (dead pid) is broken by rename
  (single winner); dead publishers' segments are reaped by
  :func:`reap_orphans` at every engine boot.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Callable

import numpy as np

from ray_tpu._private import rtlog
from ray_tpu._private.shm_store import _SHM_DIR
from ray_tpu._private.flight_recorder import _pid_alive

logger = rtlog.get("serve.llm.weights")

_HDR_LEN_BYTES = 8


def _lock_path(key: str) -> str:
    return str(_SHM_DIR / f"rtpu_llmw_{key}.lock")


def _seg_path(key: str, pid: int) -> str:
    return str(_SHM_DIR / f"rtpu_llmw_{key}.{pid}")


def _parse_pid(name: str):
    core = name[:-len(".ready")] if name.endswith(".ready") else name
    if core.endswith(".lock") or ".stale." in core:
        return None
    try:
        return int(core.rsplit(".", 1)[1])
    except (IndexError, ValueError):
        return None


def _live_segment(key: str):
    """A live publisher's segment base for ``key`` (reaping dead ones)."""
    prefix = f"rtpu_llmw_{key}."
    try:
        names = os.listdir(_SHM_DIR)
    except OSError:
        return None
    for name in names:
        if not (name.startswith(prefix) and name.endswith(".ready")):
            continue
        pid = _parse_pid(name)
        if pid is None:
            continue
        base = str(_SHM_DIR / name[:-len(".ready")])
        if _pid_alive(pid):
            return base
        for p in (str(_SHM_DIR / name), base):
            try:
                os.unlink(p)
            except OSError:
                pass
    return None


def reap_orphans() -> int:
    """Unlink weight segments whose publisher pid is dead (engine boot
    sweep — a SIGKILLed replica cannot release() its own)."""
    n = 0
    try:
        names = os.listdir(_SHM_DIR)
    except OSError:
        return n
    for name in names:
        if not name.startswith("rtpu_llmw_"):
            continue
        pid = _parse_pid(name)
        if pid is None or pid == os.getpid() or _pid_alive(pid):
            continue
        try:
            os.unlink(_SHM_DIR / name)
            n += 1
        except OSError:
            pass
    if n:
        logger.info("reaped %d orphaned weight segment file(s)", n)
    return n


def _flatten(params: Any):
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(params)
    return [np.asarray(x) for x in leaves], treedef


def release(key: str) -> None:
    """Unlink the published segment for ``key`` (engine shutdown).

    Safe at any time: attachers copy the leaves onto the device and
    close their mmap before returning, so nothing references the file
    after publish_or_attach returns — the segment is purely a cache.  A
    concurrent attacher racing the unlink sees FileNotFoundError and
    falls back to a private init.  Unlinks only THIS process's
    published segment (attachers have nothing to release); segments of
    SIGKILLed publishers are swept by :func:`reap_orphans`."""
    base = _seg_path(key, os.getpid())
    for p in (base + ".ready", base):
        try:
            os.unlink(p)
        except OSError:
            pass


def publish_or_attach(key: str, init_fn: Callable[[], Any],
                      timeout_s: float = 120.0) -> Any:
    """Return the param pytree for ``key``, shared through /dev/shm.

    First caller on the node runs ``init_fn`` and publishes; every other
    caller attaches to the published bytes (host-side zero-copy).  On
    any shm failure the caller falls back to a private ``init_fn()``.
    """
    import jax
    lock = _lock_path(key)
    deadline = time.monotonic() + timeout_s
    while True:
        live = _live_segment(key)
        if live is not None:
            try:
                return _attach(live, init_fn)
            except Exception:  # noqa: BLE001 - corrupt/raced segment
                logger.exception("attach to %s failed; loading privately",
                                 live)
                return init_fn()
        try:
            fd = os.open(lock, os.O_CREAT | os.O_EXCL | os.O_WRONLY, 0o600)
        except FileExistsError:
            # a peer is publishing; break a dead publisher's stale lock.
            # Break-by-RENAME, not unlink: rename succeeds for exactly
            # one racer (the second gets ENOENT), so two waiters can
            # never both "break" and end up publishing concurrently —
            # the loser of the rename just re-enters the O_EXCL race.
            if _lock_stale(lock):
                stale = f"{lock}.stale.{os.getpid()}"
                try:
                    os.rename(lock, stale)
                    os.unlink(stale)
                except OSError:
                    pass
                continue
            if time.monotonic() > deadline:
                logger.warning("weights publish wait timed out for %s; "
                               "loading privately", key)
                return init_fn()
            time.sleep(0.05)
            continue
        try:
            os.write(fd, str(os.getpid()).encode())
        finally:
            os.close(fd)
        params = None
        base = _seg_path(key, os.getpid())
        try:
            params = init_fn()
            _publish(base, base + ".ready", params)
        except Exception:  # noqa: BLE001 - publish best-effort
            if params is None:
                raise      # the model load itself failed: surface it
            logger.exception("weights publish for %s failed; continuing "
                             "with private params", key)
            try:
                os.unlink(base)
            except OSError:
                pass
        finally:
            try:
                os.unlink(lock)
            except OSError:
                pass
        return params


def _lock_stale(lock: str) -> bool:
    try:
        with open(lock, "rb") as f:
            pid = int(f.read().decode() or "0")
    except (OSError, ValueError):
        return False
    return pid > 0 and not _pid_alive(pid)


def _publish(base: str, ready: str, params: Any) -> None:
    leaves, _ = _flatten(params)
    metas, off = [], 0
    for a in leaves:
        metas.append(dict(shape=list(a.shape), dtype=str(a.dtype),
                          offset=off, nbytes=a.nbytes))
        off += a.nbytes
    hdr = json.dumps(metas).encode()
    # pid-unique temp: even if lock-breaking ever admitted two
    # publishers, they cannot tear each other's bytes — os.replace
    # promotes whichever finished last, atomically
    tmp = f"{base}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(len(hdr).to_bytes(_HDR_LEN_BYTES, "little"))
        f.write(hdr)
        for a in leaves:
            f.write(np.ascontiguousarray(a).tobytes())
    os.replace(tmp, base)
    with open(ready, "wb") as f:
        f.write(b"1")
    logger.info("published %d weight leaves (%.1f MB) to %s",
                len(leaves), off / 1e6, base)


def _attach(base: str, init_fn: Callable[[], Any]) -> Any:
    """Map the published segment and rebuild the pytree structure from a
    throwaway abstract init (shapes only, no device work)."""
    import jax
    import mmap as _mmap

    fd = os.open(base, os.O_RDONLY)
    try:
        size = os.fstat(fd).st_size
        mm = _mmap.mmap(fd, size, prot=_mmap.PROT_READ)
    finally:
        os.close(fd)
    hdr_len = int.from_bytes(mm[:_HDR_LEN_BYTES], "little")
    metas = json.loads(mm[_HDR_LEN_BYTES:_HDR_LEN_BYTES + hdr_len])
    body = _HDR_LEN_BYTES + hdr_len
    shapes = jax.eval_shape(init_fn)
    leaves_s, treedef = jax.tree_util.tree_flatten(shapes)
    if len(leaves_s) != len(metas):
        raise ValueError("published leaf count mismatch")
    buf = memoryview(mm)
    try:
        leaves = []
        for m in metas:
            a = np.frombuffer(buf, dtype=np.dtype(m["dtype"]),
                              count=int(np.prod(m["shape"]) or 1),
                              offset=body + m["offset"]).reshape(m["shape"])
            # jnp.asarray copies onto the device buffer, so the mmap can
            # close before returning (no dangling shared pages to leak)
            leaves.append(jax.numpy.asarray(a))
            del a
    finally:
        buf.release()
        try:
            mm.close()
        except BufferError:  # pragma: no cover - view still pinned
            pass
    logger.info("attached %d weight leaves from %s", len(leaves), base)
    return jax.tree_util.tree_unflatten(treedef, leaves)
