"""File-backed peer directory + rendezvous hashing.

Membership is a directory of JSON files (`sql.fleet.directory`), one
per live member, written atomically (tmp + rename) at join and removed
at leave. Every member — and a test harness — discovers the
fleet by listing that directory: no coordinator, no gossip protocol,
and a crashed process leaves at worst one stale file that liveness
probing (pid check on this host) or a failed fetch skims off. This is
the same posture as the shuffle block store: the data plane is
peer-to-peer, the control plane is O(metadata).

Placement is rendezvous (highest-random-weight) hashing over
`(peer_id, key)` digests: every member independently computes the same
preference ORDER for a key, and a membership change reassigns only the
keys whose top choice was the departed/joined peer — the property that
keeps fingerprint-sticky routing (and the peer-cache owner guess)
stable while processes churn.
"""
from __future__ import annotations

import hashlib
import json
import os
import time
from typing import List, Optional

__all__ = ["PeerInfo", "PeerDirectory", "rendezvous_order"]


class PeerInfo:
    """One member's registration record."""

    __slots__ = ("peer_id", "host", "port", "gw_host", "gw_port", "pid",
                 "started")

    def __init__(self, peer_id: str, host: str, port: int,
                 gw_host: Optional[str] = None,
                 gw_port: Optional[int] = None,
                 pid: Optional[int] = None,
                 started: Optional[float] = None):
        self.peer_id = peer_id
        self.host = host
        self.port = int(port)
        self.gw_host = gw_host
        self.gw_port = gw_port
        self.pid = pid if pid is not None else os.getpid()
        self.started = float(started if started is not None
                             else time.time())

    @property
    def addr(self):
        """The peer-cache server address."""
        return (self.host, self.port)

    @property
    def gateway(self):
        """The JSON-lines gateway address (None for a headless member
        that serves only the cache tier)."""
        if self.gw_host is None or self.gw_port is None:
            return None
        return (self.gw_host, int(self.gw_port))

    def to_dict(self) -> dict:
        return {"peer_id": self.peer_id, "host": self.host,
                "port": self.port, "gw_host": self.gw_host,
                "gw_port": self.gw_port, "pid": self.pid,
                "started": self.started}

    @classmethod
    def from_dict(cls, d: dict) -> "PeerInfo":
        return cls(d["peer_id"], d["host"], d["port"],
                   gw_host=d.get("gw_host"), gw_port=d.get("gw_port"),
                   pid=d.get("pid"), started=d.get("started"))

    def __repr__(self):
        return (f"PeerInfo({self.peer_id!r}, {self.host}:{self.port}, "
                f"gw={self.gateway}, pid={self.pid})")


def _alive(info: PeerInfo) -> bool:
    """Best-effort liveness: the registering pid still exists on this
    host. A pid we cannot signal (another uid, or a genuinely remote
    host whose registration carries a foreign pid space) counts as
    alive — a wrong 'alive' costs one failed fetch, a wrong 'dead'
    silently shrinks the fleet."""
    if info.pid is None:
        return True
    try:
        os.kill(info.pid, 0)
        return True
    except ProcessLookupError:
        return False
    except OSError:
        return True


class PeerDirectory:
    """The membership view over one registration directory."""

    def __init__(self, root: str):
        self.root = root

    def _path(self, peer_id: str) -> str:
        # peer ids are host:port strings; ':' is path-safe on posix but
        # keep the filename tame anyway
        return os.path.join(self.root,
                            peer_id.replace(":", "_") + ".json")

    def register(self, info: PeerInfo) -> str:
        os.makedirs(self.root, exist_ok=True)
        path = self._path(info.peer_id)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(info.to_dict(), f)
        os.replace(tmp, path)
        return path

    def deregister(self, peer_id: str) -> None:
        try:
            os.unlink(self._path(peer_id))
        except OSError:
            pass

    def peers(self, live_only: bool = True) -> List[PeerInfo]:
        """Every registered member, registration-file order-independent
        (sorted by peer_id for determinism). Corrupt/half-written files
        are skipped — registration is atomic, so these are crash
        leftovers, not protocol states."""
        out = []
        try:
            names = os.listdir(self.root)
        except OSError:
            return out
        for name in names:
            if not name.endswith(".json"):
                continue
            try:
                with open(os.path.join(self.root, name),
                          encoding="utf-8") as f:
                    info = PeerInfo.from_dict(json.load(f))
            except Exception:
                continue
            if live_only and not _alive(info):
                continue
            out.append(info)
        out.sort(key=lambda p: p.peer_id)
        return out

    def oldest_peer(self, exclude: str = None) -> Optional[PeerInfo]:
        """The designated warm-state donor: the longest-lived live
        member (it has seen the most queries — the warmest caches and
        calibration tables in the fleet)."""
        cands = [p for p in self.peers() if p.peer_id != exclude]
        if not cands:
            return None
        return min(cands, key=lambda p: (p.started, p.peer_id))


def _weight(peer_id: str, key_repr: str) -> int:
    h = hashlib.blake2b(f"{peer_id}|{key_repr}".encode("utf-8"),
                        digest_size=8)
    return int.from_bytes(h.digest(), "big")


def rendezvous_order(key, peer_ids) -> List[str]:
    """Peer ids sorted by highest-random-weight for `key` (any
    repr-stable value — plan fingerprints are tuples of primitives).
    Index 0 is the key's owner; later entries are the stable fallback
    order a router spills along and a cache consult probes."""
    kr = repr(key)
    return sorted(peer_ids, key=lambda pid: _weight(pid, kr),
                  reverse=True)
