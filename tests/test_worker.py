"""Cross-process serving worker tests (ISSUE 15): wire protocol,
RemoteReplica mirrors, worker supervision, and SIGKILL failover.

Tier-1 (not in conftest's _SLOW_MODULES), all on CPU in deterministic
``time_mode="steps"``. The load-bearing assertions:

- every RPC message survives the wire losslessly: frames round-trip,
  ``Request`` (sampling state incl. ``top_p``, generated tokens,
  timestamps, cursors) and export payloads re-materialise exactly —
  the cross-process preemption-resume contract;
- a torn frame poisons only the CONNECTION: the worker closes that
  socket and keeps serving, the client raises instead of wedging;
- greedy AND sampled streams through N real worker processes are
  BIT-IDENTICAL to an undisturbed single-engine run, and token
  timestamps match the in-process front-end exactly — one front-end
  clock domain spans the fleet (every timestamp an integral iteration
  number in ``steps`` mode);
- a real SIGKILL mid-run is detected by exit code and the mirrors fail
  the dead worker's work over bit-identically (finished == accepted);
- death detection: exit codes and heartbeat flatlines each reported
  exactly once; capacity grants spawn real processes and shrink drains
  them;
- request-lifecycle hardening (ISSUE 16): per-call RPC deadlines
  tighten from the compile-scale budget to ``rpc_timeout_s`` after the
  first step response; a SIGSTOP'd worker (hung, not dead — no exit
  code to poll) is fenced within that timeout and the fleet resumes
  bit-identically; ``cancel`` and ``deadline`` cross the wire and the
  mirrors retire identically to the in-process path.

One module-scoped supervisor (two prewarmed workers, ``reset()``
between tests) keeps the process-spawn cost to roughly one fleet
build. The ``@pytest.mark.slow`` chaos lane drives the same kill
through serve_bench's ``--workers --worker-kill`` path and the analyze
``--rpc-overhead-tol`` gate, mirroring scripts/chaos.sh.
"""

import json
import os
import socket
import struct
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_trainer.models.config import GPTConfig
from tpu_trainer.models.gpt import GPT
from tpu_trainer.serving import (
    Request,
    SamplingParams,
    ServingEngine,
    ServingFrontend,
    WorkerSupervisor,
)
from tpu_trainer.serving import remote
from tpu_trainer.serving.remote import (
    FrameError,
    MAX_FRAME_BYTES,
    ReplicaDied,
    WorkerHandle,
    encode_frame,
    load_params_npz,
    recv_frame,
    request_apply_wire,
    request_from_wire,
    request_to_wire,
    save_params_npz,
    send_frame,
)
from tpu_trainer.utils import faults
from tpu_trainer.utils.preemption import grant_capacity, read_capacity

# Same tiny model as test_frontend.py ON PURPOSE: within one pytest
# process the in-process jit cache is already warm when this module
# runs, so only the worker subprocesses pay a compile.
CFG = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
                max_seq_len=64, dropout=0.0, attention_dropout=0.0,
                dtype="float32", param_dtype="float32")
BLOCK = 8
ENGINE_KW = dict(block_size=BLOCK, attention="reference",
                 prefix_cache=True, max_batch=4)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def params():
    return GPT(CFG).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]


@pytest.fixture(scope="module")
def sup(params):
    s = WorkerSupervisor(params, CFG, engine_kwargs=ENGINE_KW)
    s.prewarm(2)
    yield s
    s.close()


def _mixed_requests(n=8, max_new=6, seed=0):
    """Shared-prefix trace mixing greedy and top-p sampled requests —
    a fresh RandomState per call, so two calls build identical traces
    (the bit-identity tests compare across separate runs)."""
    rs = np.random.RandomState(seed)
    prefix = rs.randint(1, CFG.vocab_size, size=2 * BLOCK).tolist()
    reqs = []
    for i in range(n):
        tail = rs.randint(1, CFG.vocab_size,
                          size=4 + (i % 2) * 8).tolist()
        temp = 0.0 if i % 2 == 0 else 0.8
        reqs.append(Request(
            rid=i, prompt=prefix + tail, max_new_tokens=max_new,
            sampling=SamplingParams(temperature=temp, top_p=0.9,
                                    seed=100 + i),
            arrival_time=0.0))
    return reqs


# --- wire protocol (pure python, no processes) -----------------------------

class TestFraming:
    def test_frames_round_trip_in_order(self):
        a, b = socket.socketpair()
        try:
            msgs = [{"id": 1, "method": "ping"},
                    {"id": 2, "ok": True, "result": {"deltas": [],
                                                     "load": {"q": 0}}},
                    {"unicode": "héllo", "nested": [1, [2, {"x": None}]]}]
            for m in msgs:
                send_frame(a, m)
            assert [recv_frame(b) for _ in msgs] == msgs
        finally:
            a.close()
            b.close()

    def test_clean_eof_between_frames_is_none(self):
        a, b = socket.socketpair()
        try:
            send_frame(a, {"id": 1})
            a.close()
            assert recv_frame(b) == {"id": 1}
            assert recv_frame(b) is None
        finally:
            b.close()

    @pytest.mark.parametrize("poison", [
        b"\x00\x00",                              # torn header
        struct.pack(">I", 0),                     # zero length
        struct.pack(">I", MAX_FRAME_BYTES + 1),   # oversized length
        struct.pack(">I", 100) + b"short",        # torn body
        struct.pack(">I", 4) + b"notj",           # non-JSON body
        struct.pack(">I", 4) + b"\xff\xfe\x00\x01",   # non-UTF-8 body
    ])
    def test_torn_frame_raises_frame_error(self, poison):
        a, b = socket.socketpair()
        try:
            a.sendall(poison)
            a.close()
            with pytest.raises(FrameError):
                recv_frame(b)
        finally:
            b.close()

    def test_oversized_outgoing_frame_refused(self):
        with pytest.raises(FrameError, match="exceeds max"):
            encode_frame({"blob": "x" * (MAX_FRAME_BYTES + 1)})

    def test_rpc_maps_worker_value_error_and_bad_id(self):
        a, b = socket.socketpair()
        try:
            # Pre-buffer the responses: rpc() sends, then reads what is
            # already queued on the full-duplex pair.
            send_frame(b, {"id": 1, "ok": False,
                           "error": {"type": "ValueError", "msg": "nope"}})
            with pytest.raises(ValueError, match="nope"):
                remote.rpc(a, 1, "submit", {})
            send_frame(b, {"id": 99, "ok": True, "result": {}})
            with pytest.raises(ReplicaDied, match="response id"):
                remote.rpc(a, 2, "ping", {})
            b.close()
            with pytest.raises(ReplicaDied):
                remote.rpc(a, 3, "ping", {})
        finally:
            a.close()


class TestRequestWire:
    def _request(self):
        req = Request(rid=7, prompt=[3, 1, 4, 1, 5, 9], max_new_tokens=12,
                      sampling=SamplingParams(temperature=0.7, top_k=11,
                                              top_p=0.85, seed=42),
                      arrival_time=2.0, eos_id=5)
        req.generated = [8, 2, 8]
        req.token_times = [3.0, 4.0, 5.0]
        req.status = "running"
        req.slot = 2
        req.preemptions = 1
        req.first_token_at = 3.0
        req.prefill_cursor = 6
        req.prefill_target = 6
        req.prefix_hit_tokens = 8
        req.spec_drafted, req.spec_accepted, req.spec_steps = 4, 3, 2
        req._blocks_registered = 1
        return req

    def test_request_round_trips_losslessly(self):
        req = self._request()
        # Through real JSON, exactly like the socket path.
        back = request_from_wire(json.loads(json.dumps(request_to_wire(req))))
        assert back.rid == req.rid and back.prompt == req.prompt
        assert back.sampling == req.sampling        # incl. top_p
        assert back.generated == req.generated
        assert back.token_times == req.token_times
        assert back.eos_id == req.eos_id
        assert back.arrival_time == req.arrival_time
        assert back._blocks_registered == req._blocks_registered
        for f in remote._RUNTIME_FIELDS:
            assert getattr(back, f) == getattr(req, f), f

    def test_apply_wire_syncs_runtime_state_onto_mirror(self):
        req = self._request()
        mirror = Request(rid=7, prompt=list(req.prompt), max_new_tokens=12,
                         sampling=req.sampling, arrival_time=2.0, eos_id=5)
        request_apply_wire(mirror, request_to_wire(req))
        assert mirror.generated == req.generated
        assert mirror.status == "running" and mirror.preemptions == 1
        assert mirror.prefix_hit_tokens == 8

    def test_params_npz_round_trips_nested_tree(self, tmp_path):
        tree = {"wte": {"embedding": np.arange(6, dtype=np.float32)
                        .reshape(2, 3)},
                "h_0": {"attn": {"kernel": np.ones((2, 2), np.float32)},
                        "scale": np.float32(2.5)}}
        path = str(tmp_path / "p.npz")
        save_params_npz(path, tree)
        back = load_params_npz(path)
        np.testing.assert_array_equal(back["wte"]["embedding"],
                                      tree["wte"]["embedding"])
        np.testing.assert_array_equal(back["h_0"]["attn"]["kernel"],
                                      tree["h_0"]["attn"]["kernel"])
        assert float(back["h_0"]["scale"]) == 2.5


# --- death detection without real processes --------------------------------

class _FakeProc:
    def __init__(self, rc=None):
        self.rc = rc
        self.pid = 999999

    def poll(self):
        return self.rc

    def kill(self):
        self.rc = -9

    def wait(self, timeout=None):
        return self.rc


class TestDeathDetection:
    def test_exit_code_death_reported_exactly_once(self, tmp_path):
        sup = WorkerSupervisor(None, None, run_dir=str(tmp_path / "r"))
        sup._handles[0] = WorkerHandle(worker_id=0, proc=_FakeProc(rc=137),
                                       sock=None)
        sup._handles[1] = WorkerHandle(worker_id=1, proc=_FakeProc(),
                                       sock=None)
        assert sup.poll_deaths() == [0]
        assert sup.poll_deaths() == []          # reported once
        sup._handles[1].retired = True          # deliberate shutdowns
        sup._handles[1].proc.rc = 0             # are never deaths
        assert sup.poll_deaths() == []

    def test_heartbeat_flatline_detected_and_settled(self, tmp_path):
        sup = WorkerSupervisor(None, None, run_dir=str(tmp_path / "r"),
                               heartbeat_timeout_s=0.5)
        proc = _FakeProc()                      # alive but wedged
        sup._handles[3] = WorkerHandle(worker_id=3, proc=proc, sock=None)
        beat = os.path.join(sup.heartbeat_dir, "heartbeat_host00003.jsonl")
        with open(beat, "w") as f:
            f.write(json.dumps({"kind": "heartbeat",
                                "unix": time.time() - 60}) + "\n")
        assert sup.poll_deaths() == [3]
        assert proc.rc is not None              # settled with a kill
        assert sup.poll_deaths() == []

    def test_fresh_heartbeat_is_not_a_death(self, tmp_path):
        sup = WorkerSupervisor(None, None, run_dir=str(tmp_path / "r"),
                               heartbeat_timeout_s=30.0)
        sup._handles[0] = WorkerHandle(worker_id=0, proc=_FakeProc(),
                                       sock=None)
        beat = os.path.join(sup.heartbeat_dir, "heartbeat_host00000.jsonl")
        with open(beat, "w") as f:
            f.write(json.dumps({"kind": "heartbeat",
                                "unix": time.time()}) + "\n")
        assert sup.poll_deaths() == []


# --- per-call RPC deadlines and the transport fault shim -------------------

class TestRpcTimeouts:
    """Pure socketpair, no processes: the compile-scale timeout applies
    only until the first step response; after that every call gets the
    small per-call budget, and a peer that never answers raises
    ``ReplicaDied`` instead of wedging the front-end."""

    def _handle(self, **kw):
        a, b = socket.socketpair()
        return WorkerHandle(worker_id=0, proc=_FakeProc(), sock=a,
                            **kw), a, b

    def test_timeout_tightens_after_first_step_response(self):
        h, a, b = self._handle(rpc_timeout_s=3.0, first_call_timeout_s=77.0)
        try:
            send_frame(b, {"id": 1, "ok": True, "result": {}})
            h.rpc("ping")
            assert a.gettimeout() == 77.0       # still compile-scale
            assert not h.first_step_done        # ping is not a step
            send_frame(b, {"id": 2, "ok": True,
                           "result": {"deltas": [], "load": {}}})
            h.rpc("step")
            assert h.first_step_done
            send_frame(b, {"id": 3, "ok": True, "result": {}})
            h.rpc("ping")
            assert a.gettimeout() == 3.0        # per-call from now on
        finally:
            a.close()
            b.close()

    def test_silent_peer_raises_replica_died_within_timeout(self):
        h, a, b = self._handle(rpc_timeout_s=0.2, first_call_timeout_s=0.2)
        try:
            t0 = time.perf_counter()
            with pytest.raises(ReplicaDied):
                h.rpc("ping")                   # peer never answers
            assert time.perf_counter() - t0 < 5.0
        finally:
            a.close()
            b.close()

    def test_net_delay_is_transparent_and_one_shot(self, monkeypatch):
        monkeypatch.setenv(remote.NET_DELAY_MS_ENV, "1")
        h, a, b = self._handle()
        try:
            h.net_fault = "net_delay"
            send_frame(b, {"id": 1, "ok": True, "result": {}})
            assert h.rpc("ping") == {}          # delayed, not failed
            assert h.net_fault is None          # consumed
        finally:
            a.close()
            b.close()

    @pytest.mark.parametrize("kind", ["net_drop", "net_garble", "net_hang"])
    def test_lethal_net_faults_raise_replica_died(self, kind):
        h, a, b = self._handle(rpc_timeout_s=0.2, first_call_timeout_s=0.2)
        try:
            h.net_fault = kind
            with pytest.raises(ReplicaDied):
                h.rpc("ping")
        finally:
            a.close()
            b.close()

    def test_supervisor_heartbeat_timeout_defaults_finite(self, tmp_path):
        # Flatline detection is ON unless explicitly opted out: a hung
        # worker must never be invisible by default.
        s = WorkerSupervisor(None, None, run_dir=str(tmp_path / "a"))
        assert s.heartbeat_timeout_s == remote.DEFAULT_HEARTBEAT_TIMEOUT_S
        assert s.heartbeat_timeout_s is not None
        opt_out = WorkerSupervisor(None, None, run_dir=str(tmp_path / "b"),
                                   heartbeat_timeout_s=None)
        assert opt_out.heartbeat_timeout_s is None


# --- the real fleet: bit-identity, failover, resize ------------------------

class TestWorkerFleet:
    """Ordered: each test leaves the module supervisor's pool warm for
    the next (reset() keeps processes, rebuilds engines)."""

    def _fe(self, params, sup, **kw):
        kw.setdefault("replicas", 2)
        kw.setdefault("routing", "affinity")
        kw.setdefault("time_mode", "steps")
        return ServingFrontend(params, CFG, replica_factory=sup, **kw)

    def test_streams_bit_identical_and_one_clock_domain(self, params, sup):
        eng = ServingEngine(params, CFG, **ENGINE_KW)
        want = {r.rid: list(r.generated)
                for r in eng.run(_mixed_requests(), time_mode="steps")}

        fe_in = ServingFrontend(params, CFG, replicas=2, routing="affinity",
                                time_mode="steps", **ENGINE_KW)
        fin_in = fe_in.run(_mixed_requests())
        assert {r.rid: list(r.generated) for r in fin_in} == want
        in_times = {r.rid: list(r.token_times) for r in fin_in}

        fe = self._fe(params, sup)
        fin = fe.run(_mixed_requests())
        s = fe.summary()
        assert {r.rid: list(r.generated) for r in fin} == want
        # One clock domain: the workers' timestamps ARE the front-end's
        # iteration numbers — equal to the in-process front-end on the
        # same topology, and integral in steps mode.
        got_times = {r.rid: list(r.token_times) for r in fin}
        assert got_times == in_times
        assert all(t == float(int(t))
                   for ts in got_times.values() for t in ts)
        assert s["transport"] == "rpc"
        assert s["finished"] == s["accepted"] == len(fin)
        assert s["worker_deaths"] == 0
        sup.reset()

    def test_cancel_rpc_retires_on_worker_and_mirror(self, params, sup):
        fe = self._fe(params, sup)
        reqs = _mixed_requests(6, max_new=8)
        for r in reqs:
            assert fe.submit(r).accepted
        for _ in range(3):
            fe.step()
        assert fe.cancel(reqs[2].rid)
        assert reqs[2].status == "cancelled"     # mirror synced at cancel
        assert not fe.cancel(reqs[2].rid)        # already terminal
        fin = fe.drain()
        s = fe.summary()
        # The cancelled rid never reappears in a later step delta: it is
        # counted exactly once and excluded from the finished stream.
        assert reqs[2].rid not in {r.rid for r in fin}
        assert s["cancelled"] == 1
        assert s["accepted"] == s["finished"] + s["cancelled"]
        assert s["in_flight"] == 0
        sup.reset()

    def test_deadline_expiry_crosses_the_wire(self, params, sup):
        fe = self._fe(params, sup)
        reqs = _mixed_requests(6)
        # Expires at iteration 3 (the first boundary past 2.0), long
        # before its 6 decode tokens are done — on the WORKER's engine;
        # the delta must carry the terminal state back to the mirror.
        reqs[1].deadline = 2.0
        fin = fe.run(reqs)
        s = fe.summary()
        assert reqs[1].status == "deadline_exceeded"
        assert reqs[1].finished_at == 3.0
        assert len(reqs[1].generated) < reqs[1].max_new_tokens
        assert reqs[1].rid not in {r.rid for r in fin}
        assert s["deadline_exceeded"] == 1
        assert s["deadline_miss_rate"] == 1.0    # 1 deadline, 1 miss
        assert s["accepted"] == s["finished"] + s["deadline_exceeded"]
        assert s["in_flight"] == 0
        sup.reset()

    def test_torn_frame_closes_connection_not_worker(self, sup):
        h = sup._pool[0]
        path = os.path.join(sup.run_dir, f"w{h.worker_id}.sock")
        # Free the worker's single serving loop, then poison it twice.
        h.sock.close()
        h.sock = None
        try:
            for poison in (struct.pack(">I", MAX_FRAME_BYTES + 1) + b"x",
                           struct.pack(">I", 4) + b"notj"):
                s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
                s.settimeout(30.0)
                s.connect(path)
                s.sendall(poison)
                # The worker closes the poisoned connection — as a clean
                # FIN or, when it closed with bytes still unread, a RST.
                try:
                    assert s.recv(1) == b""
                except ConnectionResetError:
                    pass
                s.close()
        finally:
            # Always hand a live connection back: later tests share this
            # pooled handle and must not inherit a dead one.
            s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            s.settimeout(120.0)
            s.connect(path)
            h.sock = s
        # The process survived: the fresh connection serves normally.
        assert remote.rpc(s, 1, "ping", {}) == {}
        hello = remote.rpc(s, 2, "hello", {})
        assert hello["pid"] == h.pid

    def test_sigkill_failover_streams_bit_identical(self, params, sup,
                                                    monkeypatch):
        eng = ServingEngine(params, CFG, **ENGINE_KW)
        want = {r.rid: list(r.generated)
                for r in eng.run(_mixed_requests(), time_mode="steps")}

        fe = self._fe(params, sup)
        # Pin the victim to the replica that owns the shared prefix, so
        # the kill really strands queued AND in-flight work.
        victim = fe._rendezvous(
            fe._affinity_key(_mixed_requests()[0].prompt), fe._live()).rid
        monkeypatch.setenv("TPU_TRAINER_FAULT_REPLICA", str(victim))
        with faults.plan("worker_kill@3"):
            fin = fe.run(_mixed_requests())
        s = fe.summary()
        assert {r.rid: list(r.generated) for r in fin} == want
        assert s["worker_deaths"] == 1
        assert s["failover_events"] == 1
        assert s["failed_over_requests"] >= 1
        assert s["replicas_live"] == 1
        assert s["finished"] == s["accepted"] == len(fin)
        assert sup.live_worker_count() == 1     # the process is really gone
        sup.reset()

    @pytest.mark.slow   # real process spawn+drain; tier-1 budget is tight
    def test_capacity_grant_spawns_and_shrink_drains_processes(
            self, params, sup, tmp_path):
        cap = str(tmp_path / "capacity.json")
        fe = self._fe(params, sup, replicas=1, capacity_file=cap,
                      max_replicas=2, capacity_probe_every=1)
        spawned_before = sup._spawned
        grant_capacity(cap, 1)
        for r in _mixed_requests(6):
            assert fe.submit(r).accepted
        fin = fe.drain()
        s = fe.summary()
        assert len(fin) == 6 and s["finished"] == s["accepted"]
        assert s["replicas_live"] == 2 and s["grows"] == 1
        assert read_capacity(cap) == 0
        # The grow was a REAL process: the pool was empty, so the
        # supervisor had to launch a new worker.
        assert sup._spawned == spawned_before + 1
        assert sup.live_worker_count() == 2

        fe.shrink(1)
        fe.drain()
        s = fe.summary()
        assert s["replicas_live"] == 1 and s["retired_replicas"] == 1
        assert sup.live_worker_count() == 1     # drained worker torn down
        sup.reset()


# --- the hung-RPC fence (SIGSTOP drill) ------------------------------------

class TestWorkerHang:
    """SIGSTOP is the nasty failure mode: the process is hung, not dead
    — no exit code to poll, heartbeats just stop. The per-call RPC
    timeout is the only detector; the supervisor then FENCES the suspect
    (SIGKILL works on stopped processes) so it can never wake up and
    write again, and the standard export/failover path resumes every
    stream bit-identically on the survivor."""

    def test_hung_worker_fenced_streams_resume_bit_identical(
            self, params, sup, monkeypatch):
        eng = ServingEngine(params, CFG, **ENGINE_KW)
        want = {r.rid: list(r.generated)
                for r in eng.run(_mixed_requests(), time_mode="steps")}

        fe = ServingFrontend(params, CFG, replica_factory=sup, replicas=2,
                             routing="affinity", time_mode="steps")
        victim = fe._rendezvous(
            fe._affinity_key(_mixed_requests()[0].prompt), fe._live()).rid
        monkeypatch.setenv("TPU_TRAINER_FAULT_REPLICA", str(victim))
        # Warm EVERY worker under the compile-scale first-call budget
        # (a fresh pool member pays its jit compile here), then tighten
        # the per-call timeout — exactly what a production deploy does
        # after warm-up. Warm requests go straight to the replicas so
        # the front-end's accounting stays clean for the assertions.
        slowest = 0.0
        for h in fe._replicas:
            rep = h.engine
            rep.submit(Request(rid=900 + h.rid, prompt=[1, 2, 3],
                               max_new_tokens=1, sampling=SamplingParams(),
                               arrival_time=0.0))
            while rep.has_work():
                t0 = time.perf_counter()
                rep.step()
                slowest = max(slowest, time.perf_counter() - t0)
            assert rep._handle.first_step_done  # warm: small budget now on
        # The detector, from what a healthy step costs HERE and NOW: the
        # run below still compiles a program for each new prefill width,
        # as the warm-up's step just did, and on a box shared with other
        # test workers (or a 1-core container timesharing the front-end
        # and both workers) that wall-clocks past a fixed 1.5 s. The hung
        # worker still trips it, and the cap keeps the stall it causes
        # under the 10 s asserted below.
        for h in fe._replicas:
            h.engine._handle.rpc_timeout_s = min(8.0, max(1.5, 2.0 * slowest))
        fenced_before = sup.n_fenced
        with faults.plan("worker_hang@3"):
            fin = fe.run(_mixed_requests())
        s = fe.summary()
        assert {r.rid: list(r.generated) for r in fin} == want
        assert s["finished"] == s["accepted"] == len(fin)
        assert s["worker_deaths"] == 1
        assert s["replicas_live"] == 1
        assert sup.n_fenced == fenced_before + 1
        assert sup.live_worker_count() == 1      # the suspect is really gone
        # The stall the front-end actually observed is bounded by the
        # per-call timeout (plus fence overhead, generous CI margin).
        assert 1.0 <= s["stall_recovery_max_s"] < 10.0
        sup.reset()


# --- the chaos lane (serve_bench --workers + analyze gates) ----------------

@pytest.mark.slow
@pytest.mark.chaos
class TestWorkerKillChaosLane:
    def test_bench_workers_lane_and_analyze_gates(self, tmp_path):
        # Transport A/B plus a real SIGKILL mid-bench: the bench's drain
        # gate asserts every ACCEPTED request finished across processes,
        # and analyze's absolute RPC-overhead gate passes on the run's
        # own records (self-compare, like scripts/chaos.sh lane 8).
        sys.path.insert(0, os.path.join(REPO, "benchmarks"))
        try:
            import serve_bench
        finally:
            sys.path.pop(0)
        out = str(tmp_path / "workers.jsonl")
        assert serve_bench.main(
            ["--smoke", "--workload", "shared_prefix", "--workers", "2",
             "--ab", "--worker-kill", "6", "--out", out]) == 0
        from tpu_trainer.tools.analyze import main as analyze_main
        assert analyze_main(
            [out, "--compare", out, "--reject-tol", "0.0",
             "--rpc-overhead-tol", "5.0", "--queue-wait-tol", "60.0"]) == 0
