"""Serving replica child: one ServingEngine behind the fleet protocol.

Launched by the :class:`~fms_fsdp_tpu.serve.fleet.FleetRouter` (via the
ReplicaSetSupervisor's spawn callback), this process speaks the
line-delimited JSON protocol on stdin/stdout documented in
serve/fleet.py: ``submit``/``resume``/``drain`` in,
``hb``/``done``/``handoff``/``reject`` out. Disaggregated fleets route
fresh requests to prefill-role replicas (whose engines retire each
stream as a packed PageHandoff) and ``resume`` the wire bytes on a
decode-role replica. stdout is the protocol channel — nothing else may
print there (jax and tracebacks go to stderr, which the router
redirects to a per-incarnation log file).

Data plane vs control plane: when the router passes ``--data-fd`` (its
end of a per-replica socketpair created at spawn), handoff frames move
as chunked, individually-acked, CRC-checked transfers on that channel
(serve/disagg/transport.py) and stdio carries only the control
messages naming them — ``handoff_begin``/``migrate`` out (frame
metadata, no payload) and ``resume`` in (with ``transfer_id``/
``total`` instead of ``data``). Without the fd, the original
single-blob base64 relay is used unchanged.

Drain-and-migrate: SIGTERM is the preemption notice. The handler only
sets a flag; the serve loop then stops admitting, hands queued rids
back (``returned``), packs each live decode stream — llama/mixtral
via the page codec, mamba via the slab codec — and ships them to the
router as ``migrate`` transfers, heartbeating while the chunks drain,
before exiting clean with the ``preempted`` registry code. A planned
eviction thus costs zero recompute; unplanned death (SIGKILL) keeps
the journal requeue path.

A heartbeat goes out after every engine iteration and on idle ticks;
the router's stall watchdog keys on its absence. Two fault sites fire
at the engine-iteration boundary (resilience/faults.py):

- ``replica_kill``: hard-exit with ``code`` (default the
  ``replica_loss`` registry code) — mid-stream replica death;
- ``replica_stall``: park in a ``seconds``-long sleep (default 3600)
  without dying — heartbeats stop, the hang the watchdog must convert
  into a kill + relaunch.

Both filter on ``replica`` (index, equality) and ``step`` (engine
iteration), so a soak schedule can kill replica 1 exactly at iteration 5
of whichever incarnation reaches it first (``FMS_FAULTS`` is inherited
through the environment; ``times=1`` stops the relaunched incarnation
from dying at its own iteration 5). The transport fault sites
(``handoff_chunk_corrupt``/``handoff_chunk_drop``/``transport_stall``)
fire inside the chunk sender / data channel, filtered by ``transport``
— this replica's channel label is ``rep<idx>``.

Engine failures exit through :func:`classified_exit` — an engine
exception classifies as ``replica_loss`` (the replica is the unit that
died; the router requeues and the supervisor relaunches), surfaced as
:class:`ReplicaLostError` so the registry's lazy classifier maps it.

Weights come from ``--params`` (a training checkpoint — pickle,
step_N_ckp dir, or checkpoints/ root) or ``--init-seed`` (deterministic
random init — two replicas or two whole fleets given the same seed serve
bit-identical greedy streams, which is what the chaos soak's
token-parity assertion keys on).
"""

import argparse
import base64
import json
import os
import signal
import sys
import threading
import time
from queue import Empty, Queue

# how long a preempted replica keeps pumping its migrate transfers
# before giving up and exiting (unfinished rids fall back to requeue)
MIGRATE_GRACE_S = 20.0


def _emit(msg: dict) -> None:
    sys.stdout.write(json.dumps(msg) + "\n")
    sys.stdout.flush()


def _stdin_reader(q: Queue) -> None:
    for line in sys.stdin:
        line = line.strip()
        if not line:
            continue
        try:
            q.put(json.loads(line))
        except ValueError:
            continue  # torn router line; the router retries via requeue
    q.put({"type": "drain"})  # stdin closed: router is gone, wind down


def build_engine(args):
    """Heavy imports live here: the module stays importable (for the
    arg parser) without jax."""
    from fms_fsdp_tpu.utils.compile_cache import configure_compile_cache

    # a relaunched replica must not pay the full compile again
    configure_compile_cache()
    import jax

    from fms_fsdp_tpu.serve.engine import ServeConfig, ServingEngine
    from fms_fsdp_tpu.serve.families import init_params_for, load_model_config

    # model construction resolves through the family registry
    # (serve/families/) — the same resolution the engine itself uses, so
    # replica and engine can never diverge on it (a llama bootstrap used
    # to be duplicated here); model_cfg.json may carry any family, with
    # an optional explicit "family" key
    with open(args.model_cfg) as f:
        model_cfg = load_model_config(json.load(f))
    with open(args.serve_cfg) as f:
        serve_cfg = ServeConfig(**json.load(f))
    if args.params:
        return ServingEngine.from_checkpoint(
            args.params, model_cfg, serve_cfg
        )
    params = init_params_for(model_cfg)(jax.random.PRNGKey(args.init_seed))
    return ServingEngine(params, model_cfg, serve_cfg)


def serve_loop(engine, replica_idx: int, idle_sleep_s: float = 0.02,
               data_fd: int = -1, preempt_evt=None):
    """The replica's life: drain router messages, step the engine,
    stream completions and heartbeats. Returns when drained; a SIGTERM
    (``preempt_evt``) instead migrates live streams and hard-exits
    ``preempted``."""
    from fms_fsdp_tpu.resilience.exits import EXIT_CODES
    from fms_fsdp_tpu.resilience.faults import fire_fault
    from fms_fsdp_tpu.serve.disagg.transport import (
        KIND_ACK,
        ChunkReceiver,
        ChunkSender,
        DataChannel,
        TransportError,
        next_transfer_id,
    )
    from fms_fsdp_tpu.serve.scheduler import RequestRejected

    inbox: Queue = Queue()
    reader = threading.Thread(
        target=_stdin_reader, args=(inbox,), daemon=True
    )
    reader.start()

    by_req = {}  # engine Request (identity) -> router rid
    draining = False
    preempting = False
    preempt_t0 = 0.0
    label = f"rep{replica_idx}"
    channel = (
        DataChannel.from_fd(data_fd, label=label) if data_fd >= 0 else None
    )
    out_senders = {}  # transfer_id -> (ChunkSender, rid)
    # transfer_id -> [ChunkReceiver, resume-msg-or-None]: data chunks
    # can race ahead of the stdio "resume" naming them, so a receiver
    # is created from the first frame and admitted once both halves
    # are present
    in_receivers = {}

    def admit_resume(meta: dict, data: bytes) -> None:
        try:
            req = engine.submit_handoff(
                data,
                max_new_tokens=meta.get("max_new_tokens"),
                deadline_s=meta.get("deadline_s"),
            )
            by_req[id(req)] = (req, meta["rid"])
        except RequestRejected as e:
            _emit({"type": "reject", "rid": meta["rid"], "reason": e.reason})
        except ValueError as e:  # HandoffError: bad wire bytes
            _emit(
                {
                    "type": "reject",
                    "rid": meta["rid"],
                    "reason": f"handoff_error: {e}",
                }
            )

    def pump_channel() -> None:
        if channel is None:
            return
        for m in channel.pump():
            if m["kind"] == KIND_ACK:
                ent = out_senders.get(m["transfer_id"])
                if ent is not None:
                    ent[0].on_ack(m)
            else:
                ent = in_receivers.get(m["transfer_id"])
                if ent is None:
                    ent = [
                        ChunkReceiver(
                            m["rid"], m["transfer_id"], m["total"],
                            label=label,
                        ),
                        None,
                    ]
                    in_receivers[m["transfer_id"]] = ent
                ent[0].on_chunk(m, channel)
        for tid in list(out_senders):
            sender, rid = out_senders[tid]
            try:
                sender.pump()
            except TransportError as e:
                # permanent transfer loss: drop the sender; the router's
                # side of the transfer times out and requeues the rid
                sys.stderr.write(
                    f"replica {replica_idx} transfer {tid} failed: {e}\n"
                )
                sys.stderr.flush()
                del out_senders[tid]
                continue
            if sender.done:
                del out_senders[tid]
        for tid in list(in_receivers):
            receiver, meta = in_receivers[tid]
            if meta is not None and receiver.complete:
                del in_receivers[tid]
                admit_resume(meta, receiver.assemble())

    def ship(kind: str, rid: int, data: bytes, ttft=None) -> None:
        """Emit a packed frame toward the router: chunked on the data
        channel when one exists, inline base64 otherwise. The control
        message carries the metadata either way — the router journals
        the bytes once they are whole."""
        msg = {"type": kind, "rid": rid, "bytes": len(data)}
        if ttft is not None:
            msg["ttft"] = ttft
        if channel is not None:
            tid = next_transfer_id()
            sender = ChunkSender(
                channel, rid, tid, data, label=label + ".tx"
            )
            out_senders[tid] = (sender, rid)
            msg.update(transfer_id=tid, total=sender.total)
        else:
            msg["data"] = base64.b64encode(data).decode("ascii")
        _emit(msg)

    # Warm up BEFORE the readiness heartbeat: the first step pays the
    # prefill + decode jit compile, which can dwarf the router's stall
    # timeout — a replica must not advertise readiness (and take
    # dispatched work) until a step is cheap. The warmup request is
    # engine-local; its completion is subtracted from the heartbeat's
    # progress count.
    warmup = engine.submit(
        [0] * min(8, engine.serve_cfg.max_seq_len // 2), 2
    )
    while engine.has_work():
        engine.step()
    warmup_completed = engine.scheduler.completed

    def heartbeat():
        h = engine.health()
        _emit(
            {
                "type": "hb",
                "replica": replica_idx,
                "iterations": int(h["iterations"]),
                "completed": int(
                    engine.scheduler.completed - warmup_completed
                ),
                "slots_busy": int(h["slots_busy"]),
                "queue_depth": int(h["queue_depth"]),
                "draining": bool(draining),
            }
        )

    def emit_finished(reqs):
        for req in reqs:
            ent = by_req.pop(id(req), None)
            if ent is None:
                continue
            if req.handoff_out is not None:
                # prefill role: the stream's pages + state, packed.
                # The router journals these bytes BEFORE forwarding
                # to a decode replica — a death on either side of a
                # half-shipped handoff replays from the journal.
                ship("handoff", ent[1], req.handoff_out, ttft=req.ttft)
                continue
            _emit(
                {
                    "type": "done",
                    "rid": ent[1],
                    "tokens": list(req.generated),
                    # engine-side time-to-first-token (a duration,
                    # so clock domains don't matter to the router)
                    "ttft": req.ttft,
                }
            )

    def emit_failed():
        # handoff imports that failed typed after admission: reject
        # back so the router requeues for re-prefill (never counted
        # as served)
        for req in engine.take_failed():
            ent = by_req.pop(id(req), None)
            if ent is not None:
                _emit(
                    {
                        "type": "reject",
                        "rid": ent[1],
                        "reason": getattr(
                            req, "fail_reason", "handoff_error: unknown"
                        ),
                    }
                )

    def return_queued():
        # whatever is still in the engine QUEUE will never run here —
        # hand it back to the router for redispatch
        for req in list(engine.scheduler.queue):
            ent = by_req.pop(id(req), None)
            if ent is not None:
                _emit({"type": "returned", "rid": ent[1]})
        engine.scheduler.queue.clear()

    heartbeat()  # readiness: the router only dispatches after this
    while True:
        # 0) preemption notice: drain, pack live streams, migrate
        if preempt_evt is not None and preempt_evt.is_set() and \
                not preempting:
            preempting = True
            draining = True
            preempt_t0 = time.monotonic()
            engine.drain()
            return_queued()
            # drain() collected the decode step in flight: a stream that
            # ended there is done, not one to migrate, and no step()
            # comes any more to return it
            emit_finished(
                [r for r, _ in by_req.values() if r.state == "finished"]
            )
            for req in engine.live_requests():
                ent = by_req.pop(id(req), None)
                if ent is None:
                    continue  # engine-local (warmup remnant)
                data = engine.pack_stream(req)
                if data is None:
                    # mid-chunked-prefill or speculative: not packable —
                    # fall back to the router's requeue/recompute path
                    _emit({"type": "returned", "rid": ent[1]})
                    continue
                ship("migrate", ent[1], data, ttft=req.ttft)

        if preempting:
            # no more engine steps: the packed frames are the streams
            # now. Pump the transfers out, keep heartbeating, then
            # exit clean with the preempted code.
            pump_channel()
            heartbeat()
            if not out_senders or (
                time.monotonic() - preempt_t0 > MIGRATE_GRACE_S
            ):
                for _, rid in out_senders.values():
                    # unfinished migrations fall back to requeue
                    _emit({"type": "returned", "rid": rid})
                sys.stderr.write(
                    f"replica {replica_idx} preempted: drained + "
                    f"migrated, exiting clean\n"
                )
                sys.stderr.flush()
                sys.stdout.flush()
                os._exit(EXIT_CODES["preempted"])
            time.sleep(0.005)
            continue

        # 1) ingest router messages
        while True:
            try:
                msg = inbox.get_nowait()
            except Empty:
                break
            if msg.get("type") == "submit":
                try:
                    req = engine.submit(
                        msg["prompt"],
                        msg["max_new_tokens"],
                        deadline_s=msg.get("deadline_s"),
                    )
                    by_req[id(req)] = (req, msg["rid"])
                except RequestRejected as e:
                    _emit(
                        {
                            "type": "reject",
                            "rid": msg["rid"],
                            "reason": e.reason,
                        }
                    )
            elif msg.get("type") == "resume":
                # disaggregation: admit by importing a packed handoff
                # (pages / slab + sampling state) instead of prefilling.
                # Chunked transport: the message names a transfer on the
                # data channel; inline: the bytes ride the message.
                if "data" in msg:
                    admit_resume(msg, base64.b64decode(msg["data"]))
                else:
                    tid = msg["transfer_id"]
                    ent = in_receivers.get(tid)
                    if ent is None:
                        in_receivers[tid] = [
                            ChunkReceiver(
                                msg["rid"], tid, msg["total"], label=label
                            ),
                            msg,
                        ]
                    else:
                        ent[1] = msg
            elif msg.get("type") == "drain":
                draining = True
                engine.drain()
                # engine.drain() stops admission; running streams finish
                return_queued()

        # 2) fault sites: the engine-iteration boundary (mid-stream
        # when requests are in flight)
        p = fire_fault(
            "replica_stall", replica=replica_idx, step=engine.iterations
        )
        if p is not None:
            time.sleep(float(p.get("seconds", 3600)))
        p = fire_fault(
            "replica_kill", replica=replica_idx, step=engine.iterations
        )
        if p is not None:
            sys.stderr.write(
                f"injected replica_kill at iteration "
                f"{engine.iterations}\n"
            )
            sys.stderr.flush()
            os._exit(int(p.get("code", EXIT_CODES["replica_loss"])))

        # 3) move transfer chunks/acks (both directions, non-blocking)
        pump_channel()

        # 4) step + stream completions
        if engine.has_work():
            emit_finished(engine.step())
            emit_failed()
            # engine-side deadline expiries (queued or in-flight) never
            # come back from step(); the router must still terminalize
            # their journal records
            for key, (req, rid) in list(by_req.items()):
                if req.state == "expired":
                    _emit({"type": "expired", "rid": rid})
                    del by_req[key]
            heartbeat()
        else:
            heartbeat()
            if draining and not out_senders and not in_receivers:
                return
            time.sleep(idle_sleep_s)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model-cfg", required=True,
                    help="JSON file of model-config fields; family "
                         "inferred from the keys or pinned by an "
                         "explicit \"family\" entry (serve/families/)")
    ap.add_argument("--serve-cfg", required=True,
                    help="JSON file of ServeConfig fields")
    ap.add_argument("--params", default="",
                    help="checkpoint path (omit to random-init)")
    ap.add_argument("--init-seed", type=int, default=0,
                    help="PRNG seed for random init when --params is unset")
    ap.add_argument("--replica", type=int, required=True,
                    help="replica index (fault-site filter key)")
    ap.add_argument("--data-fd", type=int, default=-1,
                    help="fd of this replica's data-channel socket "
                         "(chunked handoff transport); -1 = single-blob "
                         "stdio relay")
    args = ap.parse_args(argv)

    from fms_fsdp_tpu.resilience.exits import classified_exit
    from fms_fsdp_tpu.serve.fleet import ReplicaLostError

    # SIGTERM is the preemption notice: the handler only sets a flag —
    # the serve loop drains, migrates live streams to siblings through
    # the router, and exits clean (``preempted``)
    preempt_evt = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: preempt_evt.set())

    with classified_exit():
        try:
            engine = build_engine(args)
            serve_loop(
                engine,
                args.replica,
                data_fd=args.data_fd,
                preempt_evt=preempt_evt,
            )
        except (SystemExit, KeyboardInterrupt):
            raise
        except Exception as e:  # noqa: BLE001 — replica death boundary
            raise ReplicaLostError(
                f"replica {args.replica} engine failure: {e!r}"
            ) from e


if __name__ == "__main__":
    main()
