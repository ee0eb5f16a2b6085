"""Cross-host dataflow fragments: trajectory ring segments over sockets.

ROADMAP item 4's cross-process tier (the MSRL/MindSpeed "dataflow
fragment" shape — PAPERS.md arXiv 2210.00882, 2507.19017): actor HOSTS
run the existing deferred-fetch collector (rl/rollout.py) against their
own envs and ship each trajectory ring segment to ONE learner host as a
single framed message, so collect and update overlap across two real
processes/schedulers instead of sharing one.

Wire protocol — length-prefixed binary frames, one TCP or Unix-domain
stream per actor host, strictly request/response in submission order
(the learner's collects are serialised through the max_workers=1
pipeline executor, so a connection never carries interleaved requests):

    prefix  = struct "<4sBIQ" : MAGIC b"DF01", frame type,
              header bytes (u32), body bytes (u64)
    header  = pickled dict (control metadata, episode records, field
              table — never the obs arrays themselves)
    body    = the SEGMENT field payloads, raw bytes, concatenated in
              header["fields"] order; empty for control frames

SEGMENT bodies are scatter-gather written straight from the actor's
ring-segment slab views (``sendmsg`` over the field buffers — no
intermediate pickle/copy of obs arrays) and received straight into the
learner's OWN ``TrajRing`` segment views: the recv write is the
lease-time write, so the learner-side alias/ownership discipline is
byte-for-byte the existing ledger (rl/ring.py — note_staged's alias
probe, phase-2 update tokens, loud lease timeouts all unchanged).

Release-token topology (who frees what):

- LEARNER segment: leased before the recv, published after it; released
  by the canonical two-phase protocol train/loops.py already runs
  (note_staged / note_update) — nothing new on this side.
- ACTOR segment: published by ``RolloutCollector._collect_deferred``;
  its release token is an :class:`AckToken` armed by the driver after
  the segment frame is fully sent and set when the learner's ACK frame
  arrives — the ack IS the remote segment's phase-1 token (the socket
  send+recv is always a copy, so "staged == copied" holds by
  construction). A missing ack therefore surfaces as the ring's own
  loud lease timeout naming the ledger states, never as corruption.

Bit-exactness: a single actor host at depth 0 is pinned bit-exact vs
the in-process path (tests/test_fragments.py) because sampling is
replicated (mesh-size-invariant — no collectives), env seeds are the
learner's ``_collect_seed + i`` stream, and the rng keys ride the
PARAMS frames verbatim. Actor hosts sample on THEIR devices: on a CPU
test box both sides are the same XLA CPU backend; a TPU learner with
CPU actors trades bit-parity for the overlap (document, don't assert).

Teardown follows the shm discipline (CLAUDE.md): the learner owns the
listener socket, the actor processes, and its ring slabs — ``close()``
plus a ``weakref.finalize`` crash fallback; actors attach, never own.
SIGTERM on an actor host exits through ``finally`` so its vec-env
workers and shm slabs are reclaimed (kill test pins zero litter).
"""
from __future__ import annotations

import os
import pickle
import socket
import struct
import subprocess
import sys
import tempfile
import threading
import time
import traceback
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ddls_tpu import telemetry

MAGIC = b"DF01"
# magic(4) type(1) header_len(u32) body_len(u64)
_PREFIX = struct.Struct("<4sBIQ")
PREFIX_BYTES = _PREFIX.size

T_CONFIG = 1    # learner -> actor: env/model/seed build recipe
T_HELLO = 2     # actor -> learner: pid + obs field specs
T_PARAMS = 3    # learner -> actor: params snapshot + collect rng + seq
T_SEGMENT = 4   # actor -> learner: one trajectory segment (body = fields)
T_ACK = 5       # learner -> actor: segment seq consumed (phase-1 token)
T_SHUTDOWN = 6  # learner -> actor: clean exit
T_ERROR = 7     # actor -> learner: exception text (best effort)

FRAME_NAMES = {T_CONFIG: "CONFIG", T_HELLO: "HELLO", T_PARAMS: "PARAMS",
               T_SEGMENT: "SEGMENT", T_ACK: "ACK", T_SHUTDOWN: "SHUTDOWN",
               T_ERROR: "ERROR"}

# non-obs SEGMENT fields, in wire order after the obs fields
_TRAJ_FIELDS = ("actions", "logp", "values", "rewards", "dones")


# ------------------------------------------------------------------ codec
def encode_frame(ftype: int, header: Optional[dict] = None,
                 buffers: Sequence[Any] = ()) -> List[memoryview]:
    """Encode one frame as a scatter-gather buffer list (prefix+header,
    then each payload buffer verbatim — the obs arrays are never copied
    into an intermediate pickle)."""
    hdr = pickle.dumps(header if header is not None else {},
                       protocol=pickle.HIGHEST_PROTOCOL)
    views = [memoryview(b).cast("B") for b in buffers]
    body = sum(v.nbytes for v in views)
    prefix = _PREFIX.pack(MAGIC, ftype, len(hdr), body)
    return [memoryview(prefix + hdr)] + views


def frame_nbytes(parts: Sequence[memoryview]) -> int:
    return sum(p.nbytes for p in parts)


def _sendmsg_all(sock: socket.socket, parts: Sequence[memoryview]) -> int:
    """Send every buffer in ``parts`` (sendmsg scatter-gather, looping
    across partial sends); returns total bytes written."""
    pending = [p for p in parts if p.nbytes]
    total = sum(p.nbytes for p in pending)
    while pending:
        sent = sock.sendmsg(pending)
        while sent:
            if sent >= pending[0].nbytes:
                sent -= pending[0].nbytes
                pending.pop(0)
            else:
                pending[0] = pending[0][sent:]
                sent = 0
    return total


def send_frame(sock: socket.socket, ftype: int,
               header: Optional[dict] = None,
               buffers: Sequence[Any] = ()) -> int:
    return _sendmsg_all(sock, encode_frame(ftype, header, buffers))


def _recv_exact_into(sock: socket.socket, view: memoryview) -> None:
    got = 0
    n = view.nbytes
    while got < n:
        r = sock.recv_into(view[got:], n - got)
        if r == 0:
            raise ConnectionError("socket closed mid-frame")
        got += r


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray(n)
    _recv_exact_into(sock, memoryview(buf))
    return bytes(buf)


def _parse_prefix(raw: bytes) -> Tuple[int, int, int]:
    magic, ftype, hdr_len, body_len = _PREFIX.unpack(raw)
    if magic != MAGIC:
        raise ValueError(f"bad frame magic {magic!r} (want {MAGIC!r}): "
                         "stream desynchronised")
    return ftype, hdr_len, body_len


def _field_view(arr: np.ndarray) -> memoryview:
    """A flat byte view of ``arr`` — zero-copy when already contiguous
    (ring-segment prefix slices are), one copy otherwise."""
    return memoryview(np.ascontiguousarray(arr)).cast("B")


def recv_frame(sock: socket.socket,
               field_sink: Optional[Callable[[str, tuple, np.dtype],
                                             Optional[np.ndarray]]] = None
               ) -> Tuple[int, dict, Dict[str, np.ndarray]]:
    """Blocking read of one frame.

    SEGMENT bodies are streamed field-by-field per the header's field
    table: ``field_sink(name, shape, dtype)`` may return a writable
    array (e.g. a learner ring-segment view — the recv IS the
    lease-time write) or None for a fresh allocation. Returns
    ``(ftype, header, fields)``; ``fields`` is empty for control
    frames (whose payload rides the header)."""
    ftype, hdr_len, body_len = _parse_prefix(_recv_exact(sock,
                                                         PREFIX_BYTES))
    header = pickle.loads(_recv_exact(sock, hdr_len)) if hdr_len else {}
    fields: Dict[str, np.ndarray] = {}
    if body_len:
        specs = header.get("fields")
        if not specs:
            raise ValueError(
                f"{FRAME_NAMES.get(ftype, ftype)} frame carries "
                f"{body_len} body bytes but no field table")
        seen = 0
        for name, shape, dtype_str in specs:
            dtype = np.dtype(dtype_str)
            dest = field_sink(name, tuple(shape), dtype) \
                if field_sink is not None else None
            if dest is None:
                dest = np.empty(tuple(shape), dtype)
            else:
                if tuple(dest.shape) != tuple(shape) or \
                        dest.dtype != dtype:
                    raise ValueError(
                        f"field {name!r}: sink shape/dtype "
                        f"{dest.shape}/{dest.dtype} != wire "
                        f"{tuple(shape)}/{dtype}")
            _recv_exact_into(sock, _writable_byte_view(dest))
            fields[name] = dest
            seen += dest.nbytes
        if seen != body_len:
            raise ValueError(f"field table sums to {seen} bytes but "
                             f"body declared {body_len}")
    return ftype, header, fields


def _writable_byte_view(arr: np.ndarray) -> memoryview:
    if not arr.flags["C_CONTIGUOUS"]:
        raise ValueError("recv destination must be C-contiguous")
    return memoryview(arr).cast("B")


class FrameAssembler:
    """Incremental frame pump (the flight-recorder LineAssembler shape):
    feed arbitrary byte chunks, get complete ``(ftype, header, body)``
    frames out — torn prefixes/headers/bodies simply wait for more
    bytes. Control-plane convenience and the codec test surface; the
    data plane streams SEGMENT bodies with :func:`recv_frame` instead
    (fields land in their destination buffers, not a joined blob)."""

    def __init__(self):
        self._buf = bytearray()

    def feed(self, data: bytes) -> List[Tuple[int, dict, bytes]]:
        self._buf.extend(data)
        frames = []
        while True:
            if len(self._buf) < PREFIX_BYTES:
                break
            ftype, hdr_len, body_len = _parse_prefix(
                bytes(self._buf[:PREFIX_BYTES]))
            need = PREFIX_BYTES + hdr_len + body_len
            if len(self._buf) < need:
                break
            hdr = pickle.loads(bytes(
                self._buf[PREFIX_BYTES:PREFIX_BYTES + hdr_len])) \
                if hdr_len else {}
            body = bytes(self._buf[PREFIX_BYTES + hdr_len:need])
            del self._buf[:need]
            frames.append((ftype, hdr, body))
        return frames

    @property
    def pending_bytes(self) -> int:
        return len(self._buf)


# ------------------------------------------------------------- addresses
def parse_address(addr: str):
    """``unix:<path>`` -> (AF_UNIX, path); ``tcp:<host>:<port>`` ->
    (AF_INET, (host, port))."""
    if addr.startswith("unix:"):
        return socket.AF_UNIX, addr[len("unix:"):]
    if addr.startswith("tcp:"):
        host, _, port = addr[len("tcp:"):].rpartition(":")
        return socket.AF_INET, (host, int(port))
    raise ValueError(f"address must be 'unix:<path>' or "
                     f"'tcp:<host>:<port>', got {addr!r}")


def connect_address(addr: str, timeout_s: float = 30.0) -> socket.socket:
    family, target = parse_address(addr)
    deadline = time.monotonic() + timeout_s
    last_err = None
    while time.monotonic() < deadline:
        sock = socket.socket(family, socket.SOCK_STREAM)
        try:
            sock.connect(target)
            return sock
        except OSError as exc:  # listener not up yet
            last_err = exc
            sock.close()
            time.sleep(0.05)
    raise ConnectionError(f"could not connect to {addr} within "
                          f"{timeout_s}s: {last_err}")


# ----------------------------------------------------------------- tokens
class AckToken:
    """The actor-side ring release token: ``is_ready()`` flips when the
    learner's ACK frame lands (rl/ring.py's token sweep calls
    ``is_ready`` on token leaves — a plain host object is a valid
    leaf). The ack IS the remote segment's phase-1 token: the socket
    send + remote recv is always a copy, so acked == safely copied
    out of the slab, exactly the "staged tree does not alias" verdict
    of the in-process protocol."""

    __slots__ = ("_event",)

    def __init__(self):
        self._event = threading.Event()

    def set(self) -> None:
        self._event.set()

    def is_ready(self) -> bool:
        return self._event.is_set()


class _FragmentSampler:
    """The minimal learner surface ``RolloutCollector`` consumes on the
    deferred-fetch path: the algo-shared ``_sample_actions`` (PPO/
    IMPALA/PG are verbatim-identical — rl/ppo.py is the canon) plus a
    replicated obs sharding over the actor host's LOCAL mesh.
    Replicated sampling has no collectives, so its bits do not depend
    on the mesh width — the root of the cross-process parity pin."""

    def __init__(self, apply_fn):
        import jax

        from ddls_tpu.parallel.mesh import make_mesh, replicated_sharding

        self.apply_fn = apply_fn
        self.mesh = make_mesh()
        self._replicated = (replicated_sharding(self.mesh)
                            if jax.process_count() == 1 else None)

    def _sample_actions(self, params, obs, rng):
        import jax
        import jax.numpy as jnp

        logits, values = self.apply_fn(params, obs)
        actions = jax.random.categorical(rng, logits, axis=-1)
        logp = jnp.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1), actions[:, None],
            axis=-1)[:, 0]
        return actions, logp, values


# ------------------------------------------------------------ actor host
class ActorHostDriver:
    """Serve one learner connection: build the vec env + deferred-fetch
    collector from the CONFIG frame, then collect a segment per PARAMS
    frame and ship it as one SEGMENT frame (scatter-gather from the
    ring-segment views). Runs in ``scripts/actor_host.py``."""

    def __init__(self, sock: socket.socket):
        self.sock = sock
        self.vec_env = None
        self.collector = None
        self.host_index: Optional[int] = None
        self._obs_keys: Tuple[str, ...] = ()
        self._pending: Dict[int, AckToken] = {}
        self.bytes_sent = 0
        self.segments_sent = 0

    # -- build -----------------------------------------------------------
    def _build(self, cfg: dict) -> None:
        import jax

        from ddls_tpu.models.policy import batched_policy_apply
        from ddls_tpu.rl.rollout import (OBS_KEYS, ParallelVectorEnv,
                                         RolloutCollector, VectorEnv)
        from ddls_tpu.train.loops import build_policy_from_model_config
        from ddls_tpu.utils.common import get_class_from_path, \
            seed_everything

        self.host_index = int(cfg["host_index"])
        self._obs_keys = tuple(cfg.get("obs_keys") or OBS_KEYS)
        B = int(cfg["num_envs"])
        T = int(cfg["rollout_length"])
        env_cls = get_class_from_path(cfg["env_cls"])
        env_config = cfg["env_config"]
        # host 0's env seed stream is EXACTLY the learner's in-process
        # stream (_collect_seed + i) — the bit-parity pin; later hosts
        # extend it contiguously
        seeds = [int(cfg["env_seed_base"]) + i for i in range(B)]
        seed_everything(int(cfg["global_seed"]))
        if cfg.get("use_parallel_envs", True):
            self.vec_env = ParallelVectorEnv(
                env_cls, env_config, B, seeds=seeds,
                backend=cfg.get("vec_env_backend", "auto"))
        else:
            self.vec_env = VectorEnv(
                [(lambda: env_cls(**env_config)) for _ in range(B)],
                seeds=seeds)
        self.vec_env.reset()
        model = build_policy_from_model_config(int(cfg["n_actions"]),
                                               cfg.get("model_config"))
        sampler = _FragmentSampler(
            lambda p, o: batched_policy_apply(model, p, o))
        self._sampler = sampler
        self.collector = RolloutCollector(
            self.vec_env, sampler, T, deferred_fetch=True,
            # 2 segments suffice at ANY learner depth: the learner acks
            # seq k inside collect k, before PARAMS k+1 ever hits the
            # wire, so at most one actor segment is un-acked at a time
            ring_segments=int(cfg.get("actor_ring_segments", 2)))
        self.collector._needs_reset = False
        self._jax = jax

    def _hello(self) -> dict:
        from ddls_tpu.rl.shm import obs_field_specs

        specs = obs_field_specs(self.vec_env.obs[0], self._obs_keys)
        return {"pid": os.getpid(),
                "host_index": self.host_index,
                "num_envs": self.vec_env.num_envs,
                "obs_specs": {k: (tuple(shape), np.dtype(dt).str)
                              for k, (shape, dt) in specs.items()}}

    # -- serve loop ------------------------------------------------------
    def serve(self) -> None:
        try:
            ftype, cfg, _ = recv_frame(self.sock)
            if ftype != T_CONFIG:
                raise ValueError(f"expected CONFIG, got "
                                 f"{FRAME_NAMES.get(ftype, ftype)}")
            self._build(cfg)
            send_frame(self.sock, T_HELLO, self._hello())
            while True:
                ftype, header, _ = recv_frame(self.sock)
                if ftype == T_ACK:
                    token = self._pending.pop(int(header["seq"]), None)
                    if token is not None:
                        token.set()
                elif ftype == T_PARAMS:
                    self._collect_and_send(header)
                elif ftype == T_SHUTDOWN:
                    break
                else:
                    raise ValueError(
                        f"unexpected frame "
                        f"{FRAME_NAMES.get(ftype, ftype)} on actor host "
                        f"{self.host_index}")
        except (ConnectionError, BrokenPipeError, EOFError):
            # learner went away: exit quietly through finally-cleanup —
            # the learner side raises the loud error
            pass
        except BaseException as exc:
            if not isinstance(exc, SystemExit):
                try:
                    send_frame(self.sock, T_ERROR,
                               {"message": repr(exc),
                                "traceback": traceback.format_exc()})
                except OSError:
                    pass
            raise

    def _collect_and_send(self, header: dict) -> None:
        jax = self._jax
        seq = int(header["seq"])
        params = header["params"]
        if self._sampler._replicated is not None:
            params = jax.device_put(params, self._sampler._replicated)
        rng = jax.numpy.asarray(header["rng"])
        t0 = time.perf_counter()
        out = self.collector.collect(params, rng)
        wall = time.perf_counter() - t0
        traj = out["traj"]
        names, table, buffers = [], [], []
        for k in self._obs_keys:
            arr = traj["obs"][k]
            table.append((f"obs:{k}", tuple(arr.shape), arr.dtype.str))
            buffers.append(_field_view(arr))
        for name in _TRAJ_FIELDS:
            arr = np.asarray(traj[name])
            table.append((name, tuple(arr.shape), arr.dtype.str))
            buffers.append(_field_view(arr))
        lv = np.asarray(out["last_values"])
        table.append(("last_values", tuple(lv.shape), lv.dtype.str))
        buffers.append(_field_view(lv))
        seg_header = {"seq": seq, "fields": table,
                      "episodes": out["episodes"],
                      "env_steps": int(out["env_steps"]),
                      "collect_wall_s": wall,
                      "host_index": self.host_index}
        n = send_frame(self.sock, T_SEGMENT, seg_header, buffers)
        self.bytes_sent += n
        self.segments_sent += 1
        ring = out.get("ring")
        if ring is not None:
            # the ack is the phase-1 token (see module docstring); armed
            # AFTER the send completes so the slab views were fully read
            token = AckToken()
            ring.set_release_token(out["ring_segment"], token,
                                   generation=out["ring_generation"])
            self._pending[seq] = token

    def close(self) -> None:
        if self.collector is not None and hasattr(self.collector, "close"):
            try:
                self.collector.close()
            except Exception:
                pass
        if self.vec_env is not None:
            try:
                self.vec_env.close()
            except Exception:
                pass
            self.vec_env = None
        try:
            self.sock.close()
        except OSError:
            pass


# -------------------------------------------------- learner-side consumer
def _actor_host_script() -> str:
    import ddls_tpu

    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(ddls_tpu.__file__))), "scripts", "actor_host.py")


def _teardown(conns: list, procs: list, paths: list) -> None:
    """Crash-fallback teardown (weakref.finalize target — must not hold
    the LearnerFragment): close fds, escalate SIGTERM->SIGKILL, unlink
    the socket path. Mirrors rl/shm.py's parent-owned discipline."""
    for conn in conns:
        try:
            conn.close()
        except OSError:
            pass
    for proc in procs:
        if proc.poll() is None:
            proc.terminate()
    deadline = time.monotonic() + 5.0
    for proc in procs:
        if proc.poll() is None:
            try:
                proc.wait(timeout=max(deadline - time.monotonic(), 0.1))
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=5.0)
    for path in paths:
        try:
            os.unlink(path)
        except OSError:
            pass
    for path in paths:
        d = os.path.dirname(path)
        try:
            if d and d.startswith(tempfile.gettempdir()):
                os.rmdir(d)
        except OSError:
            pass


class _HostHandle:
    __slots__ = ("conn", "proc", "host_index", "pid", "segments", "acks",
                 "transit_sum", "transit_max", "bytes_recv")

    def __init__(self, conn, proc, host_index):
        self.conn = conn
        self.proc = proc
        self.host_index = host_index
        self.pid = None
        self.segments = 0
        self.acks = 0
        self.transit_sum = 0.0
        self.transit_max = 0.0
        self.bytes_recv = 0

    def describe(self) -> str:
        state = "alive"
        if self.proc is not None and self.proc.poll() is not None:
            state = f"exited rc={self.proc.returncode}"
        return f"actor host {self.host_index} (pid {self.pid}, {state})"


class LearnerFragment:
    """The learner-side collector duck-type over N actor-host
    connections (train/loops.py ``collect_transport='socket'``).

    ``collect(params, rng)`` round-robins the hosts: device_get the
    params snapshot (explicit — transfer-guard-legal) and ship it with
    the rng key as one PARAMS frame, lease a segment of the learner's
    OWN TrajRing, stream the SEGMENT frame's obs fields straight into
    that segment's views (the recv write IS the lease-time write), ACK,
    publish, and return the same out-dict shape as
    ``RolloutCollector._collect_deferred`` — so the loop's canonical
    note_staged/note_update two-phase release runs unchanged, plus
    ``segment_transit_s`` (wire+serialisation lag net of the actor's
    own collect wall time — clock-skew-free because both spans are
    single-clock durations) as ``params_age_updates``'s sibling."""

    def __init__(self, *, env_cls_path: str, env_config: dict,
                 model_config, n_actions: int, num_envs: int,
                 rollout_length: int, collect_seed: int, global_seed: int,
                 ring_segments: int, num_actor_hosts: int = 1,
                 transport: str = "unix", tcp_host: str = "127.0.0.1",
                 tcp_port: int = 0, use_parallel_envs: bool = True,
                 vec_env_backend: str = "auto",
                 actor_ring_segments: int = 2,
                 connect_timeout_s: float = 120.0,
                 recv_timeout_s: float = 300.0,
                 spawn: bool = True, actor_env: Optional[dict] = None,
                 allow_device: bool = False):
        from ddls_tpu.rl.ring import TrajRing
        from ddls_tpu.rl.rollout import OBS_KEYS

        if num_actor_hosts < 1:
            raise ValueError("num_actor_hosts must be >= 1")
        self.num_envs = int(num_envs)
        self.rollout_length = int(rollout_length)
        self._obs_keys = OBS_KEYS
        self._recv_timeout_s = float(recv_timeout_s)
        self._seq = 0
        self._rr = 0
        self._closed = False
        self.bytes_sent = 0
        self.bytes_recv = 0
        self.segments_recv = 0
        self.env_steps_recv = 0
        self._needs_reset = False  # loops-compat; envs live on the actors

        self._sock_dir = None
        self._sock_path = None
        if transport == "unix":
            self._sock_dir = tempfile.mkdtemp(prefix="ddls_frag_")
            self._sock_path = os.path.join(self._sock_dir, "learner.sock")
            self._listener = socket.socket(socket.AF_UNIX,
                                           socket.SOCK_STREAM)
            self._listener.bind(self._sock_path)
            self.address = f"unix:{self._sock_path}"
        elif transport == "tcp":
            self._listener = socket.socket(socket.AF_INET,
                                           socket.SOCK_STREAM)
            self._listener.setsockopt(socket.SOL_SOCKET,
                                      socket.SO_REUSEADDR, 1)
            self._listener.bind((tcp_host, int(tcp_port)))
            host, port = self._listener.getsockname()[:2]
            self.address = f"tcp:{host}:{port}"
        else:
            raise ValueError(f"transport must be 'unix' or 'tcp', got "
                             f"{transport!r}")
        self._listener.listen(num_actor_hosts)
        self._listener.settimeout(connect_timeout_s)

        self._procs: List[subprocess.Popen] = []
        if spawn:
            script = _actor_host_script()
            # the child pins ITSELF to the CPU unless --allow-device
            # (scripts/actor_host.py): this learner may hold the
            # accelerator, which belongs to one process
            child_env = dict(os.environ)
            child_env.update(actor_env or {})
            argv = [sys.executable, script, "--connect", self.address]
            if allow_device:
                argv.append("--allow-device")
            for _ in range(num_actor_hosts):
                self._procs.append(subprocess.Popen(argv, env=child_env))

        self._handles: List[_HostHandle] = []
        # parent-owned lifecycle with a crash fallback, the shm
        # discipline: lists (not self) ride the finalizer
        self._final_conns: list = [self._listener]
        self._final_paths: list = ([self._sock_path]
                                   if self._sock_path else [])
        self._finalizer = weakref.finalize(
            self, _teardown, self._final_conns, self._procs,
            self._final_paths)

        config = {"env_cls": env_cls_path, "env_config": env_config,
                  "model_config": model_config, "n_actions": int(n_actions),
                  "num_envs": self.num_envs,
                  "rollout_length": self.rollout_length,
                  "global_seed": int(global_seed),
                  "use_parallel_envs": bool(use_parallel_envs),
                  "vec_env_backend": vec_env_backend,
                  "actor_ring_segments": int(actor_ring_segments),
                  "obs_keys": list(OBS_KEYS)}
        obs_specs = None
        try:
            for i in range(num_actor_hosts):
                try:
                    conn, _ = self._listener.accept()
                except socket.timeout:
                    raise RuntimeError(
                        f"actor host {i} never connected to "
                        f"{self.address} within {connect_timeout_s}s "
                        f"({self._describe_procs()})") from None
                conn.settimeout(self._recv_timeout_s)
                handle = _HostHandle(
                    conn, self._procs[i] if i < len(self._procs) else None,
                    host_index=i)
                self._final_conns.append(conn)
                cfg = dict(config)
                cfg["host_index"] = i
                # host 0 == the in-process seed stream (bit parity);
                # host j extends it by whole-host strides
                cfg["env_seed_base"] = int(collect_seed) + i * self.num_envs
                send_frame(conn, T_CONFIG, cfg)
                ftype, hello, _ = recv_frame(conn)
                if ftype == T_ERROR:
                    raise RuntimeError(
                        f"actor host {i} failed during build:\n"
                        f"{hello.get('traceback', hello.get('message'))}")
                if ftype != T_HELLO:
                    raise RuntimeError(
                        f"actor host {i}: expected HELLO, got "
                        f"{FRAME_NAMES.get(ftype, ftype)}")
                handle.pid = hello.get("pid")
                specs = {k: (tuple(s), np.dtype(d))
                         for k, (s, d) in hello["obs_specs"].items()}
                if obs_specs is None:
                    obs_specs = specs
                elif specs != obs_specs:
                    raise RuntimeError(
                        f"actor host {i} obs specs disagree with host 0: "
                        f"{specs} != {obs_specs}")
                self._handles.append(handle)

            # the learner's OWN ring: recv targets, parent-owned shm
            # slabs, canonical two-phase release — byte-for-byte the
            # in-process ledger
            missing = [k for k in OBS_KEYS if k not in obs_specs]
            if missing:
                raise RuntimeError(f"actor obs specs missing {missing}")
            self.ring = TrajRing({k: obs_specs[k] for k in OBS_KEYS},
                                 self.rollout_length + 1, self.num_envs,
                                 int(ring_segments))
        except BaseException:
            self.close()
            raise

    # -- helpers ---------------------------------------------------------
    def _describe_procs(self) -> str:
        if not self._procs:
            return "no spawned processes"
        return ", ".join(
            f"pid {p.pid}: "
            f"{'alive' if p.poll() is None else f'exited rc={p.returncode}'}"
            for p in self._procs)

    def _dead(self, handle: _HostHandle, why: str) -> RuntimeError:
        return RuntimeError(
            f"{handle.describe()} died mid-collect on {self.address}: "
            f"{why} — its trajectory segment is lost; restart the run "
            f"(fragments have no mid-epoch failover)")

    # -- the collector contract -----------------------------------------
    def collect(self, params, rng) -> Dict[str, Any]:
        import jax

        if self._closed:
            raise RuntimeError("LearnerFragment is closed")
        handle = self._handles[self._rr]
        self._rr = (self._rr + 1) % len(self._handles)
        self._seq += 1
        seq = self._seq
        T = self.rollout_length

        # explicit host fetch of the snapshot — the ONLY way params
        # leave the device here, so the steady-state transfer-guard pin
        # (tests/test_fragments.py) stays valid
        host_params = jax.device_get(params)
        rng_np = np.asarray(jax.device_get(rng))
        try:
            with telemetry.transfer("fragments.params", "h2h") as tr:
                n = send_frame(handle.conn, T_PARAMS,
                               {"seq": seq, "params": host_params,
                                "rng": rng_np})
                tr.add(host_params)
            self.bytes_sent += n
            t0 = time.perf_counter()
            seg = self.ring.lease()
            fields = self._recv_segment(handle, seg, seq)
            transit = max(
                time.perf_counter() - t0
                - float(fields["header"]["collect_wall_s"]), 0.0)
            n = send_frame(handle.conn, T_ACK, {"seq": seq})
            self.bytes_sent += n
        except (ConnectionError, BrokenPipeError, EOFError,
                socket.timeout) as exc:
            raise self._dead(handle, repr(exc)) from exc
        handle.acks += 1
        handle.transit_sum += transit
        handle.transit_max = max(handle.transit_max, transit)
        self.ring.publish(seg)
        header = fields["header"]
        if telemetry.enabled():
            hi = handle.host_index
            telemetry.inc(f"fragments.h{hi}.segments")
            telemetry.inc(f"fragments.h{hi}.acks")
            telemetry.observe(f"fragments.h{hi}.transit_s", transit)
        self.segments_recv += 1
        self.env_steps_recv += int(header["env_steps"])
        out = {
            "traj": {"obs": {k: seg.views[k][:T] for k in self._obs_keys},
                     "actions": fields["actions"],
                     "logp": fields["logp"],
                     "values": fields["values"],
                     "rewards": fields["rewards"],
                     "dones": fields["dones"]},
            "last_values": fields["last_values"],
            "episodes": header["episodes"],
            "env_steps": int(header["env_steps"]),
            "ring": self.ring,
            "ring_segment": seg,
            "ring_generation": seg.generation,
            "segment_transit_s": transit,
            "actor_host": handle.host_index,
        }
        return out

    def _recv_segment(self, handle: _HostHandle, seg, seq: int) -> dict:
        T = self.rollout_length

        def sink(name: str, shape: tuple, dtype: np.dtype):
            if name.startswith("obs:"):
                # the recv write IS the lease-time write: straight into
                # the leased segment's slab rows, no staging copy
                key = name[len("obs:"):]
                dest = seg.views[key][:T]
                return dest
            return None  # fresh per-collect allocation (host arrays)

        with telemetry.transfer("fragments.segment", "h2h") as tr:
            ftype, header, fields = recv_frame(handle.conn,
                                               field_sink=sink)
            if ftype == T_ERROR:
                raise self._dead(
                    handle, f"remote error:\n"
                    f"{header.get('traceback', header.get('message'))}")
            if ftype != T_SEGMENT:
                raise self._dead(handle,
                                 f"expected SEGMENT, got "
                                 f"{FRAME_NAMES.get(ftype, ftype)}")
            if int(header["seq"]) != seq:
                raise self._dead(handle,
                                 f"segment seq {header['seq']} != "
                                 f"expected {seq}")
            tr.add(fields)
        nbytes = sum(v.nbytes for v in fields.values())
        self.bytes_recv += nbytes
        handle.segments += 1
        handle.bytes_recv += nbytes
        fields["header"] = header
        return fields

    # -- reporting -------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        per_host = {}
        for h in self._handles:
            per_host[f"h{h.host_index}"] = {
                "pid": h.pid,
                "segments": h.segments,
                "acks": h.acks,
                "bytes_recv": h.bytes_recv,
                "transit_mean_s": (h.transit_sum / h.segments
                                   if h.segments else None),
                "transit_max_s": h.transit_max,
            }
        return {
            "num_actor_hosts": len(self._handles),
            "segments": self.segments_recv,
            "bytes_sent": self.bytes_sent,
            "bytes_recv": self.bytes_recv,
            "collect_bytes_per_step": (
                (self.bytes_sent + self.bytes_recv) / self.env_steps_recv
                if self.env_steps_recv else None),
            "per_host": per_host,
        }

    # -- lifecycle -------------------------------------------------------
    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        for handle in self._handles:
            try:
                send_frame(handle.conn, T_SHUTDOWN, {})
            except OSError:
                pass
        # grace period for the actors' own finally-cleanup (env workers,
        # shm slabs) before the finalizer's SIGTERM->SIGKILL escalation
        deadline = time.monotonic() + 10.0
        for proc in self._procs:
            if proc.poll() is None:
                try:
                    proc.wait(timeout=max(deadline - time.monotonic(),
                                          0.1))
                except subprocess.TimeoutExpired:
                    pass
        ring = getattr(self, "ring", None)
        if ring is not None:
            ring.close()
        # finalizer does fd close + SIGTERM->SIGKILL escalation + unlink
        self._finalizer()

    def __del__(self):  # pragma: no cover - belt and braces
        try:
            self.close()
        except Exception:
            pass
