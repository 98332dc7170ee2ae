"""Worker: serves its topology-assigned block ranges over the wire protocol.

Covers the reference worker (cake-core/src/cake/worker.rs): resolve own topology
entry by name with first-entry fallback (worker.rs:73-93), load ONLY the assigned
blocks (worker.rs:95-108), accept master connections, per-connection handshake then
an op loop, per-connection KV-cache isolation (worker.rs:52-61), and periodic
throughput stats (worker.rs:19, 253-264).

TPU-first differences:
  * Each owned contiguous range is ONE jitted lax.scan over stacked params — the
    whole span executes as a single XLA computation per request, instead of the
    reference's per-block kernel walk (worker.rs:218-229).
  * KV caches are preallocated fixed-shape buffers donated through the jit, not
    concat-grown tensors.
  * RESET lets a master start a new sequence on a live connection; errors return
    a structured ERROR frame instead of dropping the connection.

Failure semantics (the recovery half of runtime/faults.py): a FORWARD frame
carrying ``sid``/``seq`` headers is served from an EPOCH-SCOPED SESSION that
survives the connection — KV caches keyed by sid in a bounded LRU, each
remembering the last applied seq and its encoded reply. A master that lost a
reply (socket died mid-round-trip) reconnects and RESENDS the same (sid, seq):
if the op was applied, the cached reply returns without re-execution; if it
never arrived, it executes now. Either way the outcome is idempotent. A seq
gap or an evicted/unknown session returns a coded ERROR
(proto.ERR_BAD_SEQ / ERR_UNKNOWN_SESSION) so the client escalates to
full-history replay (serialized path) or failure isolation (engine path)
instead of burning retries.
"""

from __future__ import annotations

import logging
import select
import socket
import threading
import time
from collections import OrderedDict
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from cake_tpu import __version__
from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.cache import KVCache, init_cache
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.ops.rope import model_rope_tables
from cake_tpu.obs.timeline import timeline
from cake_tpu.parallel.topology import Topology
from cake_tpu.runtime import faults, proto
from cake_tpu.utils import metrics, trace

log = logging.getLogger("cake_tpu.worker")

NUM_OPS_TO_STATS = 5  # parity with worker.rs:19

# Replay sessions kept per worker: enough for a few masters' live epochs plus
# stragglers; LRU-evicted beyond this (an evicted session answers
# ERR_UNKNOWN_SESSION, which clients recover from — correctness never depends
# on retention, only fast-path replay does).
MAX_SESSIONS = 8


class _ConnectionTorn(Exception):
    """Internal: a fault spec asked for this connection to die mid-op."""


class _Session:
    """One epoch's replayable state: KV caches + the last applied op.

    ``lock`` serializes op execution per session: a retried (sid, seq) can
    arrive on a NEW connection while the original connection's thread is
    still executing that seq — the second thread must wait, then observe
    ``seq == last_seq`` and replay the cached reply instead of re-executing.
    """

    __slots__ = ("caches", "last_seq", "last_reply", "lock")

    def __init__(self, caches):
        self.caches = caches
        self.last_seq = -1
        self.last_reply: bytes | None = None
        self.lock = threading.Lock()


def wire_to_jax(t: proto.WireTensor, compute_dtype: jnp.dtype) -> jnp.ndarray:
    arr = t.to_numpy()
    if t.dtype == "bf16":
        return jnp.asarray(arr).view(jnp.bfloat16).astype(compute_dtype)
    if t.dtype == "f32" and np.dtype(compute_dtype).name == "bfloat16":
        # Narrow on host (native RTNE codec or ml_dtypes — bit-identical to the
        # on-device convert): halves the host->device upload for f32 senders.
        from cake_tpu import native

        return jnp.asarray(native.f32_to_bf16(arr)).view(jnp.bfloat16)
    return jnp.asarray(arr).astype(compute_dtype)


def jax_to_wire(x: jnp.ndarray) -> proto.WireTensor:
    if x.dtype == jnp.bfloat16:
        arr = np.asarray(x.view(jnp.uint16))
        return proto.WireTensor.from_numpy(arr, dtype_tag="bf16")
    return proto.WireTensor.from_numpy(np.asarray(x))


class Worker:
    """Block-range server bound to one topology node."""

    def __init__(
        self,
        name: str,
        model_dir: str | Path,
        topology: Topology,
        address: tuple[str, int],
        *,
        dtype: jnp.dtype = jnp.bfloat16,
        max_seq_len: int | None = None,
        batch_size: int = 1,
        attention_impl: str | None = None,
        fusion_impl: str | None = None,
        quantize: str | None = None,
        kv_dtype: jnp.dtype | None = None,
        io_timeout_s: float = 120.0,
    ):
        from cake_tpu.io.safetensors_io import load_params

        self.config = LlamaConfig.from_model_dir(
            model_dir, attention_impl=attention_impl
        )
        if fusion_impl not in (None, "none"):
            # Decode op fusion (--fusion) rides the worker's config exactly
            # like attention_impl: the norm fusion sites live in the block
            # forward THIS process runs.
            import dataclasses

            from cake_tpu.ops.fuse import parse_fusion_spec

            parse_fusion_spec(fusion_impl)  # raises on a malformed spec
            self.config = dataclasses.replace(
                self.config, fusion_impl=fusion_impl
            )
        if name not in topology.nodes and topology.nodes:
            # First-entry fallback, mirroring worker.rs:81-88.
            fallback = next(iter(topology.nodes))
            log.warning("worker name %r not in topology, using %r", name, fallback)
            name = fallback
        self.name = name
        self.dtype = dtype
        # KV storage dtype (--kv-dtype): f8 halves this worker's cache
        # memory and per-token cache bandwidth; activations stay ``dtype``.
        self.kv_dtype = dtype if kv_dtype is None else kv_dtype
        self._max_seq = int(max_seq_len or self.config.max_position_embeddings)
        self._batch = batch_size

        plan = topology.stage_plan(self.config.num_hidden_layers)
        # A replica member serves its group PRIMARY's plan ranges: the
        # stage plan names only the first-declared node of each replica
        # group (parallel/topology.py), but every member must load and
        # serve the identical spans so the master's router can swap them
        # freely (runtime/router.py).
        groups = topology.replica_groups()
        primary = next(
            (p for p, members in groups.items() if name in members), name
        )
        self.ranges = [(s.lo, s.hi) for s in plan if s.node == primary]
        if not self.ranges:
            raise ValueError(f"topology assigns no layers to worker {name!r}")

        if quantize not in (None, "int8", "int4"):
            raise ValueError(f"unknown quantize mode {quantize!r}")
        t0 = time.perf_counter()
        self.range_params = {
            (lo, hi): load_params(
                model_dir, self.config, dtype, layer_range=(lo, hi)
            )["layers"]
            for lo, hi in self.ranges
        }
        if quantize:
            # Weight-only int8/int4 on the worker's own block ranges: halves/
            # quarters this worker's weight HBM traffic; wire activations stay
            # full dtype.
            from cake_tpu.ops.quant import quantize_layer_tree

            self.range_params = {
                r: quantize_layer_tree(p, quantize)
                for r, p in self.range_params.items()
            }
        # Fuse QKV / gate|up per range (ops/fuse.py): fewer ops per scanned
        # layer, column-identical numerics (commutes with the quantize above).
        from cake_tpu.ops.fuse import fuse_layer_tree

        self.range_params = {
            r: fuse_layer_tree(p) for r, p in self.range_params.items()
        }
        log.info(
            "worker %s loaded layers %s in %.2fs",
            name,
            self.ranges,
            time.perf_counter() - t0,
        )
        trace.log_memory(f"worker.{name}.loaded")

        cfg = self.config
        cos, sin = model_rope_tables(cfg, self._max_seq)

        def run_blocks(layers, x, kv, pos, cached_prefill=False):
            return M.blocks_forward(
                layers, x, kv, cos, sin, pos, cfg, cached_prefill=cached_prefill
            )

        self._run = jax.jit(
            run_blocks,
            static_argnames=("cached_prefill",),
            donate_argnames=("kv",),
        )

        # Left-padded LOCKSTEP batch ops (continuous batching over the wire,
        # runtime/batch_backend.DistributedBatchBackend): the same pad-aware
        # batched bodies every in-process backend runs, so the TCP deployment
        # serves B concurrent rows per round trip instead of one request at a
        # time behind the API lock (the reference quirk, api/mod.rs:76).
        from cake_tpu.models.llama.batch import make_lockstep_range_ops

        from cake_tpu.obs.jitwatch import tracked_jit

        run_bprefill, run_bdecode, run_bjoin, run_bverify = (
            make_lockstep_range_ops(cfg, cos, sin)
        )
        self._run_bprefill = tracked_jit(
            run_bprefill, name="worker.batch_prefill", donate_argnames=("kv",)
        )
        self._run_bdecode = tracked_jit(
            run_bdecode, name="worker.batch_decode", donate_argnames=("kv",)
        )
        self._run_bjoin = tracked_jit(
            run_bjoin, name="worker.batch_join", donate_argnames=("kv",)
        )
        self._run_bverify = tracked_jit(
            run_bverify, name="worker.batch_verify", donate_argnames=("kv",)
        )

        self._sock = socket.create_server(address, reuse_port=False)
        self.address = self._sock.getsockname()
        # Per-connection IO deadline: a peer that stalls MID-FRAME (or never
        # finishes the handshake) releases this thread after io_timeout_s;
        # idle waits between frames are exempt (the loop treats a clean
        # zero-byte timeout as a poll tick — proto._recv_exact distinguishes).
        self.io_timeout_s = io_timeout_s
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []
        self._conns: set[socket.socket] = set()
        self._conns_lock = threading.Lock()
        # Epoch-scoped replay sessions (module docstring), sid -> _Session.
        self._sessions: OrderedDict[str, _Session] = OrderedDict()
        self._sessions_lock = threading.Lock()

    # ------------------------------------------------------------- caches

    def _fresh_caches(self, batch: int | None = None) -> dict[tuple[int, int], KVCache]:
        """Per-connection KV state (the reference's per-client cache clone,
        worker.rs:52-61). ``batch`` sizes the cache rows; a connection's caches
        are re-made at the incoming batch whenever a new sequence (pos == 0)
        arrives with a different batch dim. Rows share one position stream
        (blocks_forward has no per-row pads), so this serves EQUAL-LENGTH
        (pad-free) batches; left-padded lockstep layouts (models/llama/batch.py)
        need the local backend, which passes per-row positions directly."""
        cfg = self.config
        return {
            (lo, hi): init_cache(
                hi - lo,
                batch or self._batch,
                self._max_seq,
                cfg.num_key_value_heads,
                cfg.head_dim,
                self.kv_dtype,
            )
            for lo, hi in self.ranges
        }

    # ------------------------------------------------------------ sessions

    def _session(self, sid: str, seq: int) -> _Session | None:
        """Resolve (creating at seq 0) the replay session for ``sid``.

        None = unknown session at seq > 0: the state this op depends on is
        gone (worker restarted, or LRU-evicted) — the caller answers with a
        coded ERROR and the client escalates to its own replay/recovery.
        """
        with self._sessions_lock:
            sess = self._sessions.get(sid)
            if sess is not None:
                self._sessions.move_to_end(sid)
                return sess
            if seq != 0:
                return None
            sess = self._sessions[sid] = _Session(self._fresh_caches())
            while len(self._sessions) > MAX_SESSIONS:
                evicted, _ = self._sessions.popitem(last=False)
                log.info("session %s evicted (LRU, cap %d)", evicted,
                         MAX_SESSIONS)
            return sess

    def _drop_session(self, sid: str) -> None:
        with self._sessions_lock:
            self._sessions.pop(sid, None)

    def _drop_all_sessions(self) -> None:
        """The 'crash' fault: what a process restart does to replay state."""
        with self._sessions_lock:
            self._sessions.clear()

    # ------------------------------------------------------------- serving

    def serve_forever(self) -> None:
        log.info("worker %s listening on %s", self.name, self.address)
        self._sock.settimeout(0.5)
        while not self._stop.is_set():
            try:
                conn, peer = self._sock.accept()
            except socket.timeout:
                continue
            except OSError:
                break
            # Register BEFORE spawning the thread: stop() must see every
            # accepted socket, or a just-accepted connection could leak a
            # thread parked in recv.
            with self._conns_lock:
                self._conns.add(conn)
            if self._stop.is_set():
                # stop() may have snapshotted _conns between accept() and the
                # registration above; registration-then-check closes that race
                # (either stop() sees the socket, or we see the flag).
                try:
                    conn.close()
                except OSError:
                    pass
                break
            t = threading.Thread(
                target=self._serve_connection, args=(conn, peer), daemon=True
            )
            t.start()
            self._threads = [x for x in self._threads if x.is_alive()]
            self._threads.append(t)

    def start(self) -> threading.Thread:
        t = threading.Thread(target=self.serve_forever, daemon=True)
        self._serve_thread = t
        t.start()
        return t

    def stop(self) -> None:
        self._stop.set()
        try:
            self._sock.close()
        except OSError:
            pass
        # Accepted sockets are blocking; threads parked in recv() would never
        # observe _stop. Closing the connections unblocks and ends them, which
        # also releases their per-connection KV caches.
        with self._conns_lock:
            conns = list(self._conns)
        for c in conns:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass
        # Join the accept loop and connection threads (bounded): a daemon
        # thread still inside a jitted op while the interpreter tears down
        # can abort the process from XLA's C++ teardown — stop() returning
        # means the worker's threads are actually gone.
        serve_t = getattr(self, "_serve_thread", None)
        if serve_t is not None and serve_t is not threading.current_thread():
            serve_t.join(timeout=5.0)
        for t in self._threads:
            if t.is_alive() and t is not threading.current_thread():
                t.join(timeout=5.0)

    def _worker_info(self, latency_ms: float) -> proto.WorkerInfo:
        dev = jax.devices()[0]
        return proto.WorkerInfo(
            dtype={"bfloat16": "bf16", "float16": "f16", "float32": "f32"}[
                jnp.dtype(self.dtype).name
            ],
            device=dev.platform,
            device_count=jax.device_count(),
            latency_ms=latency_ms,
            ranges=[list(r) for r in self.ranges],
            batch_ops=True,   # understands the FORWARD ``batch`` header
            verify_ops=True,  # understands the ``verify`` batch kind
            stats_ops=True,   # answers STATS pulls + clock-stamped PINGs
        )

    def _stats_report(self, frame: proto.Frame) -> dict:
        """One node's telemetry snapshot for a STATS pull (runtime/proto.py).

        The report is the NODE-ATTRIBUTED slice of this process's telemetry:
        metric series carrying ``node=<this worker>``, flight events and
        timeline events stamped with it. In a real deployment that is
        everything the worker records (worker-side series/spans all label
        themselves — the ``unbounded-metric-label`` rule's bounded ``node``
        convention); in a single-process test cluster it also keeps a pulled
        report from echoing the master's own events back at it.
        """
        header = frame.header
        ev_cap = max(0, int(header.get("events", 256)))
        tl_cap = max(0, int(header.get("timeline", 4096)))
        dump = metrics.registry.dump()
        mine = []
        for m in dump["metrics"]:
            series = [
                s for s in m["series"]
                if s["labels"].get("node") == self.name
            ]
            if series:
                mine.append({**m, "series": series})
        events = [
            e for e in metrics.flight.snapshot()
            if e.get("node") == self.name
        ]
        tl = [
            e for e in timeline.snapshot()
            if e.get("node") == self.name
        ]
        return {
            "node": self.name,
            "wall": round(time.time(), 6),
            "metrics": {"metrics": mine},
            "events": events[-ev_cap:] if ev_cap else [],
            "timeline": tl[-tl_cap:] if tl_cap else [],
        }

    def _serve_connection(self, conn: socket.socket, peer) -> None:
        log.info("connection from %s", peer)
        # IO deadline (see __init__). The select-gated loop below only lets
        # this cover MID-frame stalls; idle waits are unbounded.
        conn.settimeout(self.io_timeout_s)
        # Legacy per-connection KV, allocated LAZILY on the first sid-less
        # FORWARD: heartbeat probes (PING-only connections) and session-
        # carrying masters (KV lives in self._sessions) never pay for a
        # full per-connection cache set.
        caches = None
        ops = 0
        read_bytes = 0
        write_bytes = 0
        window_start = time.perf_counter()
        try:
            with conn:
                # Handshake: Hello -> WorkerInfo with measured read latency
                # (worker.rs:165-182).
                t0 = time.perf_counter()
                first = proto.read_frame(conn)
                latency_ms = (time.perf_counter() - t0) * 1e3
                if first.type != proto.MsgType.HELLO:
                    proto.write_frame(
                        conn, proto.error_frame("expected HELLO")
                    )
                    return
                # The HELLO carries the master's package version; a skew is
                # legal (capability flags gate features) but worth a line in
                # the log when a wire bug is being chased.
                peer_version = first.header.get("version", "?")
                if peer_version != __version__:
                    log.warning(
                        "master version %s != worker version %s "
                        "(capability flags negotiate features; mind wire "
                        "changes)",
                        peer_version,
                        __version__,
                    )
                proto.write_frame(
                    conn, proto.worker_info_frame(self._worker_info(latency_ms))
                )

                while not self._stop.is_set():
                    # Idle wait OUTSIDE the frame read: select until bytes
                    # arrive (re-checking _stop), so io_timeout_s only ever
                    # measures MID-frame progress. Once readable, any
                    # timeout from the read means a peer stalled mid-frame
                    # (both the Python and native codecs raise TimeoutError
                    # there) — the stream is torn, drop the connection.
                    ready, _, _ = select.select([conn], [], [], 0.5)
                    if not ready:
                        continue
                    try:
                        frame = proto.read_frame(conn)
                    except (ConnectionError, TimeoutError, OSError):
                        break
                    if frame.type == proto.MsgType.RESET:
                        sid = frame.header.get("sid")
                        if sid is None:
                            caches = None  # dropped; re-made on next use
                        else:
                            self._drop_session(sid)
                        continue
                    if frame.type == proto.MsgType.PING:
                        spec = faults.check("worker.ping", node=self.name)
                        if spec is not None and spec.kind == "stall":
                            faults.sleep(spec)  # a wedged worker, as the
                            # heartbeat monitor sees one
                        # The reply carries this worker's wall clock: the
                        # prober estimates the clock offset from the RTT
                        # midpoint (obs/cluster.py), which is what lets a
                        # merged Perfetto export align this node's spans.
                        proto.write_frame(
                            conn, proto.ping_frame(t=time.time())
                        )
                        continue
                    if frame.type == proto.MsgType.STATS:
                        # Federated telemetry pull: a read-only snapshot —
                        # it touches no caches or replay sessions, so a
                        # STATS mid-session is replay-safe by construction
                        # (pinned by tests/test_cluster_obs.py).
                        proto.write_frame(
                            conn,
                            proto.stats_reply_frame(
                                self._stats_report(frame)
                            ),
                        )
                        continue
                    if frame.type != proto.MsgType.FORWARD:
                        proto.write_frame(
                            conn,
                            proto.error_frame(f"unexpected {frame.type.name}"),
                        )
                        continue

                    read_bytes += len(frame.payload)
                    t_op = time.perf_counter()
                    try:
                        # Timeline: the op is a span on this worker's node
                        # (pid) with the wire hop's flow arrow landing inside
                        # it ("f" under the frame's flow id) — the receiving
                        # half of the master's connected cross-node view.
                        kind = frame.header.get("batch", {}).get(
                            "kind", "chunk"
                        )
                        with timeline.span(
                            f"worker.{kind}",
                            rid=frame.header.get("trace"),
                            node=self.name,
                            track="ops",
                            args={"pos": frame.header.get("pos")},
                        ):
                            flow_id = frame.header.get("flow")
                            if flow_id is not None:
                                timeline.flow_end(
                                    flow_id, "hop", node=self.name,
                                    track="ops",
                                )
                            spec = faults.check("worker.op", node=self.name)
                            if spec is not None:
                                if spec.kind == "stall":
                                    faults.sleep(spec)
                                elif spec.kind in ("kill", "crash"):
                                    if spec.kind == "crash":
                                        # Process death: replay state is gone
                                        # too, not just the transport.
                                        self._drop_all_sessions()
                                    raise _ConnectionTorn()
                            caches, out_bytes, served = self._serve_forward(
                                frame, caches, conn
                            )
                        if not served:
                            continue  # replay / coded error: not a fresh op
                    except _ConnectionTorn:
                        break  # fault plan: die mid-op, no reply
                    except (ConnectionError, OSError):
                        break  # peer went away while we replied
                    except Exception as e:  # structured error, keep connection
                        log.exception("forward failed")
                        proto.write_frame(conn, proto.error_frame(str(e)))
                        continue
                    # Per-op telemetry, attributable to the master's request
                    # via the propagated trace id (the structured successor of
                    # the reference's ops/s log lines, worker.rs:253-264).
                    metrics.registry.histogram(
                        "cake_worker_op_seconds",
                        "Seconds per served FORWARD op (decode+compute+reply).",
                    ).observe(
                        time.perf_counter() - t_op,
                        node=self.name,
                        kind=kind,
                    )
                    write_bytes += out_bytes
                    ops += 1
                    if ops % NUM_OPS_TO_STATS == 0:
                        dt = time.perf_counter() - window_start
                        log.info(
                            "%s: %.1f ops/s, read %.1f KiB/s, write %.1f KiB/s",
                            peer,
                            NUM_OPS_TO_STATS / dt,
                            read_bytes / dt / 1024,
                            write_bytes / dt / 1024,
                        )
                        read_bytes = write_bytes = 0
                        window_start = time.perf_counter()
        finally:
            with self._conns_lock:
                self._conns.discard(conn)
            log.info("connection from %s closed", peer)

    def _record_op_bytes(self, rx: int, tx: int) -> None:
        """Payload bytes per direction — same unit as the master's
        cake_wire_bytes_total (frame prefix+header excluded), so the two
        ends of a hop reconcile."""
        wb = metrics.registry.counter(
            "cake_worker_bytes_total",
            "Tensor payload bytes served, by direction.",
        )
        wb.inc(rx, node=self.name, direction="rx")
        wb.inc(tx, node=self.name, direction="tx")

    def _serve_forward(self, frame, caches, conn):
        """Route one FORWARD through session replay or the legacy per-
        connection caches; execute, reply, and update replay state.

        Returns (caches, bytes_written, served): served False = the frame
        was answered from replay state or with a coded error — no fresh op
        ran, so the caller skips the per-op telemetry for it.
        """
        sid = frame.header.get("sid")
        if sid is None:
            # Legacy contract: per-connection caches, no replay.
            if caches is None:
                caches = self._fresh_caches()
            out, caches = self._execute(frame, caches)
            written = self._send_reply(
                conn, proto.encode_frame(
                    proto.tensor_frame(out, trace=frame.header.get("trace"))
                ),
            )
            self._record_op_bytes(len(frame.payload), len(out.data))
            return caches, written, True

        seq = int(frame.header.get("seq", 0))
        sess = self._session(sid, seq)
        if sess is None:
            proto.write_frame(conn, proto.error_frame(
                f"session {sid!r} unknown at seq {seq} (restarted or "
                "evicted); state must be rebuilt",
                code=proto.ERR_UNKNOWN_SESSION,
            ))
            return caches, 0, False
        with sess.lock:
            if seq == sess.last_seq and sess.last_reply is not None:
                # Idempotent replay: the op already applied, only its reply
                # was lost on the wire — answer from the cache, do NOT
                # re-execute (the KV writes must not happen twice).
                metrics.registry.counter(
                    "cake_worker_replays_total",
                    "FORWARD ops answered from the session replay cache "
                    "(duplicate sid/seq after a reconnect).",
                ).inc(node=self.name)
                metrics.flight.record(
                    "op-replayed", frame.header.get("trace"),
                    node=self.name, seq=seq,
                )
                conn.sendall(sess.last_reply)
                return caches, len(sess.last_reply), False
            if seq != sess.last_seq + 1:
                proto.write_frame(conn, proto.error_frame(
                    f"seq {seq} does not follow applied seq "
                    f"{sess.last_seq} for session {sid!r}",
                    code=proto.ERR_BAD_SEQ,
                ))
                return caches, 0, False
            out, sess.caches = self._execute(frame, sess.caches)
            data = proto.encode_frame(
                proto.tensor_frame(out, trace=frame.header.get("trace"))
            )
            # Commit replay state BEFORE the send: if the reply is lost on
            # the wire, the retried (sid, seq) must find it here.
            sess.last_seq, sess.last_reply = seq, data
        written = self._send_reply(conn, data)
        self._record_op_bytes(len(frame.payload), len(out.data))
        return caches, written, True

    def _send_reply(self, conn: socket.socket, data: bytes) -> int:
        """Send an encoded reply frame, honoring worker.reply fault specs
        (drop = never send — the op applied, the reply is lost; truncate =
        partial frame then tear the connection down)."""
        spec = faults.check("worker.reply", node=self.name)
        if spec is not None:
            if spec.kind == "drop":
                return 0
            if spec.kind == "truncate":
                conn.sendall(data[: max(1, int(len(data) * spec.frac))])
                raise _ConnectionTorn()
            if spec.kind == "delay":
                faults.sleep(spec)
        conn.sendall(data)
        return len(data)

    def _execute(self, frame, caches):
        """Run one FORWARD op; returns (out WireTensor, caches)."""
        ranges = [tuple(r) for r in frame.header["ranges"]]
        pos = frame.header["pos"]
        trace_id = frame.header.get("trace")
        if trace_id is not None:
            log.debug("op trace=%s pos=%s ranges=%s", trace_id, pos, ranges)
        x = wire_to_jax(frame.tensor(), self.dtype)
        if "batch" in frame.header:
            return self._forward_batch(frame, ranges, pos, x, caches)
        cache_batch = next(iter(caches.values())).k.shape[1]
        if x.shape[0] != cache_batch:
            if pos == 0:
                # New sequence at a new batch size: re-make this connection's
                # caches to match (batch>1 lockstep masters share the worker
                # protocol with single-stream ones).
                caches = self._fresh_caches(batch=int(x.shape[0]))
            else:
                raise ValueError(
                    f"batch changed mid-sequence: cache has {cache_batch} "
                    f"rows, activation has {x.shape[0]} (pos={pos}); "
                    "RESET or restart at pos 0 first"
                )
        for r in ranges:
            if r not in self.range_params:
                raise ValueError(f"range {r} not owned (have {self.ranges})")
            x, caches[r] = self._run(
                self.range_params[r],
                x,
                caches[r],
                jnp.int32(pos),
                # Chunked-prefill continuation: a multi-token chunk at pos > 0
                # must attend over the cache prefix, not just within itself.
                cached_prefill=M.is_cached_prefill(pos, x.shape[1]),
            )
        return jax_to_wire(x), caches

    def _forward_batch(self, frame, ranges, pos, x, caches):
        """Lockstep batch op over this connection's caches (see run_b* jits).

        Kinds: "prefill" (pos 0, fresh B-row caches), "decode" (one token at
        slot == pos), "join" (single row scattered into ``lane``).
        """
        b = frame.header["batch"]
        kind = b["kind"]
        pads = jnp.asarray(b["pads"], jnp.int32)
        if kind == "prefill":
            # Every epoch starts here: re-make this connection's caches at
            # the incoming batch (stale prior-epoch state must never leak).
            caches = self._fresh_caches(batch=int(x.shape[0]))
        else:
            cache_batch = next(iter(caches.values())).k.shape[1]
            if kind == "join":
                if int(x.shape[0]) != 1:
                    raise ValueError(
                        f"join expects a single row, got {int(x.shape[0])}"
                    )
                if int(b["lane"]) >= cache_batch:
                    raise ValueError(
                        f"join lane {b['lane']} out of range for batch "
                        f"{cache_batch}"
                    )
            elif kind in ("decode", "verify") and int(x.shape[0]) != cache_batch:
                raise ValueError(
                    f"batch {kind} with {int(x.shape[0])} rows against "
                    f"{cache_batch}-row caches; prefill the epoch first"
                )
        for r in ranges:
            if r not in self.range_params:
                raise ValueError(f"range {r} not owned (have {self.ranges})")
            if kind == "prefill":
                x, caches[r] = self._run_bprefill(
                    self.range_params[r], x, caches[r], pads,
                    jnp.asarray(b["ends"], jnp.int32),
                )
            elif kind == "decode":
                x, caches[r] = self._run_bdecode(
                    self.range_params[r], x, caches[r], pads, jnp.int32(pos)
                )
            elif kind == "join":
                x, caches[r] = self._run_bjoin(
                    self.range_params[r], x, caches[r], pads,
                    jnp.asarray(b["ends"], jnp.int32), jnp.int32(b["lane"]),
                )
            elif kind == "verify":
                # Speculative verify: a cached chunk written at slot == pos.
                x, caches[r] = self._run_bverify(
                    self.range_params[r], x, caches[r], pads, jnp.int32(pos)
                )
            else:
                raise ValueError(f"unknown batch kind {kind!r}")
        return jax_to_wire(x), caches
