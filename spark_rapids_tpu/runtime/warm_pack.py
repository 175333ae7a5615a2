"""AOT warm packs: persist the program-cache key set, preload it at
service startup.

PR 6's persistent XLA cache (`.jax_cache/host-<fp>`) made *re*-compiles
across processes cheap, but a fresh service still pays the full trace +
cache-deserialize tail inline, on the first user-visible query per
shape. A warm pack moves that tail to startup: a recording session
writes a manifest of (a) the SQL texts it served and (b) every stable
program-cache key it compiled, with a zero-fill recipe for each key's
input signature (`program_cache._args_spec`). Preload re-plans the
recorded SQL — reconstructing the builder closures and repopulating the
program-cache registry — then compiles every recorded signature through
the background pool (`runtime/compile_pool.py`) as SPECULATIVE tasks,
so a query arriving mid-preload is never queued behind warm-up work.

Safety posture mirrors the persistent cache it extends:

- the manifest is bound to the jaxlib version, the mesh fingerprint
  (device kind + count) and a format version; a mismatch logs one
  warning and preloads nothing — programs traced for another
  installation or topology must not be reconstructed here.
- a corrupt/unreadable pack logs a warning, never raises: warm-up is
  advisory.
- keys carrying identity fallbacks (`('id', N)` / `('inst', N)`) are
  excluded at record time — they cannot match across processes (the
  `unstable-program-key` lint rule polices the sources).
- `SRTPU_COMPILE_CACHE=0` hard-disables record and preload alongside
  the persistent cache.
- preload is idempotent: `CachedProgram.prewarm` skips keys that are
  already warm, so restarting a service against the same pack re-does
  no work.
"""
from __future__ import annotations

import logging
import os
import pickle
import threading
from typing import Optional

__all__ = ["VERSION", "enabled", "record_path", "note_query", "save",
           "preload", "recorded_queries", "reset", "build_manifest",
           "preload_manifest"]

log = logging.getLogger(__name__)

VERSION = 1

_lock = threading.Lock()
_queries: list = []          # recorded sql texts, insertion-ordered
_queries_set: set = set()
_QUERIES_CAP = 256


def enabled() -> bool:
    """False when SRTPU_COMPILE_CACHE=0: the warm pack is an extension
    of the persistent compile cache and obeys its kill switch."""
    return os.environ.get("SRTPU_COMPILE_CACHE") != "0"


def record_path(conf) -> Optional[str]:
    from ..config import WARM_PACK_RECORD
    p = str(conf.get(WARM_PACK_RECORD) or "").strip()
    return p if p and enabled() else None


def note_query(sql_text: str, conf) -> None:
    """Record one served SQL text (session.sql calls this when
    sql.service.warmPack.record is set)."""
    if not sql_text or record_path(conf) is None:
        return
    with _lock:
        if sql_text in _queries_set or len(_queries) >= _QUERIES_CAP:
            return
        _queries.append(sql_text)
        _queries_set.add(sql_text)


def recorded_queries() -> list:
    with _lock:
        return list(_queries)


def reset() -> None:
    """Drop recorded state (tests)."""
    with _lock:
        del _queries[:]
        _queries_set.clear()


def _fingerprint() -> str:
    import jaxlib
    from ..parallel.mesh import mesh_fingerprint
    # packs are per-topology: a manifest recorded against an 8-device
    # mesh carries sharded collective signatures that can never warm a
    # 1-device process (and would waste its compile-pool budget), so
    # the device kind + visible device count gates the load
    return f"jaxlib-{jaxlib.__version__}|{mesh_fingerprint()}"


def build_manifest(conf=None) -> dict:
    """The manifest dict `save` persists — also the fleet warm-state
    payload a member serves to a joining peer (fleet/member.py), which
    ships it over the wire instead of through a file. Same content
    either way: recorded SQL + every stable observed program spec,
    bound to this host's cache/mesh fingerprint (the RECEIVER gates on
    it, exactly like load_manifest)."""
    from . import program_cache
    programs = [p for p in program_cache.observed_programs()
                if program_cache.key_stable(p["base_key"])]
    return {"version": VERSION, "fingerprint": _fingerprint(),
            "queries": recorded_queries(), "programs": programs}


def save(conf, path: Optional[str] = None) -> Optional[str]:
    """Write the manifest: recorded SQL + every stable observed program
    spec. Returns the path written, or None when recording is disabled
    and no explicit path was given. Atomic (tmp + rename): a reader
    never sees a half-written pack."""
    if not enabled():
        return None
    path = path or record_path(conf)
    if not path:
        return None
    manifest = build_manifest(conf)
    tmp = f"{path}.tmp.{os.getpid()}"
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(tmp, "wb") as f:
        pickle.dump(manifest, f)
    os.replace(tmp, path)
    return path


def load_manifest(path: str) -> Optional[dict]:
    """Read + validate a pack. None (with one warning) on any problem:
    missing file, unpicklable bytes, wrong version, wrong host
    fingerprint — a warm pack must never take the service down."""
    if not enabled():
        return None
    try:
        with open(path, "rb") as f:
            m = pickle.load(f)
    except FileNotFoundError:
        log.warning("warm pack %s not found; starting cold", path)
        return None
    except Exception as e:  # noqa: BLE001 — corrupt pack is advisory
        log.warning("warm pack %s is unreadable (%r); starting cold",
                    path, e)
        return None
    return m if _validate_manifest(m, path) else None


def _validate_manifest(m, source: str) -> bool:
    """Version + host-fingerprint gate, shared by the file path and
    the fleet wire path — a peer's manifest is as foreign as a file
    recorded on another box and gets exactly the same scrutiny."""
    if not isinstance(m, dict) or m.get("version") != VERSION:
        log.warning("warm pack %s has version %r (want %d); ignoring",
                    source, m.get("version") if isinstance(m, dict)
                    else None, VERSION)
        return False
    fp = _fingerprint()
    if m.get("fingerprint") != fp:
        log.warning(
            "warm pack %s was recorded on host fingerprint %s; this "
            "host is %s — programs may embed foreign microarch target "
            "options, ignoring the pack", source,
            m.get("fingerprint"), fp)
        return False
    return True


def preload(session, path: Optional[str] = None) -> dict:
    """Replay the pack's queries (rebuilding — and, by default,
    compiling — every program in their trees), then background-compile
    any recorded signature still cold. Returns a summary dict;
    {"status": "skipped"} when disabled/invalid. Never raises."""
    from ..config import WARM_PACK_PATH
    conf = session.conf
    path = path or str(conf.get(WARM_PACK_PATH) or "").strip()
    if not path or not enabled():
        return {"status": "skipped"}
    m = load_manifest(path)
    if m is None:
        return {"status": "skipped"}
    return preload_manifest(session, m, validated=True)


def preload_manifest(session, m: dict, validated: bool = False) -> dict:
    """Preload from an in-memory manifest (the fleet cold-join pull
    hands the donor's manifest straight here). Validates unless the
    caller already did."""
    if not enabled() or m is None:
        return {"status": "skipped"}
    if not validated and not _validate_manifest(m, "<peer>"):
        return {"status": "skipped"}
    from ..config import WARM_PACK_REPLAY
    conf = session.conf
    from . import compile_pool, program_cache
    # seed the observed-spec table first: even for sites the replay
    # below cannot resolve to a live program (missing tables on this
    # host), launch-time stage-ahead prewarm can still find the
    # recorded signatures when a real query constructs the site
    seeded = program_cache.seed_observed(m.get("programs", ()))
    replay = bool(conf.get(WARM_PACK_REPLAY))
    planned = 0
    roots = []
    for sql in m.get("queries", ()):
        try:
            df = session.sql(sql)
            if replay:
                # full replay: one throwaway execution compiles every
                # program the query dispatches, including the ones
                # built lazily inside execute_partition that a
                # plan-only pass cannot reach. Runs through normal
                # admission, so the busy hook parks speculative pool
                # work during it.
                df.collect()
            else:
                # plan-only: constructs the exec tree — every
                # construction-time cached_program registers its
                # base_key. Roots are retained on the summary so the
                # registry entries stay alive until the prewarms run.
                root, _ = df._execute(conf)
                roots.append(root)
            planned += 1
        except Exception:
            # table moved / data absent on this host: warm what we can
            continue
    pool = compile_pool.get_pool(conf)
    matched = submitted = 0
    for entry in m.get("programs", ()):
        try:
            prog = program_cache.lookup_program(entry["base_key"])
        except TypeError:
            prog = None
        if prog is None:
            continue
        matched += 1
        thunk = program_cache.prewarm_thunk(prog, entry["spec"])
        if pool is None:
            # pool disabled: compile inline at startup (still off the
            # query path — we ARE startup)
            try:
                args = thunk()
                if args is not None:
                    prog.prewarm(args)
                submitted += 1
            except Exception:
                program_cache.note_background_failure()
            continue
        if pool.submit(prog, thunk, speculative=True):
            submitted += 1
    summary = {"status": "ok", "queries": len(m.get("queries", ())),
               "queries_planned": planned, "seeded": seeded,
               "programs": len(m.get("programs", ())),
               "programs_matched": matched, "submitted": submitted,
               "_roots": roots}
    return summary
