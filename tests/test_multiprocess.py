"""Process-boundary integration: real OS processes, real sockets.

VERDICT r3 ask #3: start >=2 server processes, route a workflow over the
wire, kill one host, observe shard steal + range-ID fencing across the
network. Reference: common/rpc/factory.go:27-90 (transport),
cmd/server/cadence/server.go:271-278 (role dispatch), shard fencing
shard/context.go:586-700.

The store server owns the authoritative stores (the DB role): every CAS
and range fence evaluates THERE, which is exactly why fencing holds across
host processes.
"""
import signal
import time

import pytest

from cadence_tpu.core.enums import CloseStatus, WorkflowState
from cadence_tpu.engine.membership import shard_id_for_workflow
from cadence_tpu.rpc.cluster import launch
from cadence_tpu.rpc.wire import call as wire_call

DOMAIN = "mp-domain"
TL = "mp-tl"
NUM_SHARDS = 8


@pytest.fixture(scope="module")
def cluster():
    c = launch(num_hosts=2, num_shards=NUM_SHARDS)
    try:
        c.frontend(0).register_domain(DOMAIN)
        yield c
    finally:
        c.stop()


def drive_workflow(fe, workflow_id: str, deadline_s: float = 30.0) -> None:
    """Hand-rolled worker against the wire frontend (host/taskpoller.go
    analog): poll decision tasks until this workflow's arrives, complete it."""
    from cadence_tpu.core.enums import DecisionType
    from cadence_tpu.engine.history_engine import Decision

    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        resp = fe.poll_for_decision_task(DOMAIN, TL, wait_seconds=0.5)
        if resp is None or resp.token is None:
            continue
        if resp.token.workflow_id != workflow_id:
            continue
        fe.respond_decision_task_completed(resp.token, [
            Decision(DecisionType.CompleteWorkflowExecution,
                     {"result": b"done"})])
        return
    raise TimeoutError(f"no decision task for {workflow_id}")


def wf_on_host(owned, host):
    """A workflow id hashing to a shard the given host owns."""
    for i in range(256):
        wf = f"wf-{host}-{i}"
        if shard_id_for_workflow(wf, NUM_SHARDS) in owned[host]:
            return wf
    raise AssertionError(f"no workflow id hashes onto {host}'s shards")


class TestWireCluster:
    def test_workflow_end_to_end_over_the_wire(self, cluster):
        """Start on one host's frontend, poll/respond through the other's:
        every hop (frontend→history, matching rendezvous, store writes)
        crosses a process boundary."""
        fe0, fe1 = cluster.frontend(0), cluster.frontend(1)
        fe0.start_workflow_execution(DOMAIN, "wf-wire", "wiretype", TL)
        drive_workflow(fe1, "wf-wire")
        ms = fe0.describe_workflow_execution(DOMAIN, "wf-wire")
        assert ms.execution_info.state == WorkflowState.Completed
        assert ms.execution_info.close_status == CloseStatus.Completed

    def test_hosts_without_a_device_tier_open_no_backend(self, cluster):
        """/health states the backend a host opened: none, for a host
        with no device tier on (it touches JAX only if a reset or a
        rebuild routes to it)."""
        import json
        import urllib.request

        for name, port in cluster.http_ports.items():
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/health", timeout=10) as resp:
                doc = json.loads(resp.read())
            assert doc["name"] == name and doc["device"] is None

    def test_cross_process_range_fence(self, cluster):
        """A usurper (this test process) acquires a shard through the store
        server; the old owner's CACHED engine then writes through its stale
        context and MUST be fenced — three processes, one authoritative
        range CAS (shard/context.go:586-700 across the network)."""
        from cadence_tpu.engine.persistence import ShardOwnershipLostError
        from cadence_tpu.engine.shard import ShardContext
        from cadence_tpu.rpc.client import RemoteStores

        fe0 = cluster.frontend(0)
        owned = cluster.owned_shards()
        wf = wf_on_host(owned, "host-0")
        fe0.start_workflow_execution(DOMAIN, wf, "wiretype", TL)
        domain_id = fe0.describe_domain(DOMAIN).domain_id

        # usurp the shard from a third process (this one), over the wire
        sid = shard_id_for_workflow(wf, NUM_SHARDS)
        usurper = ShardContext(sid, "usurper",
                               RemoteStores(("127.0.0.1",
                                             cluster.store_port)))
        usurper.acquire()

        # the deposed owner's cached engine writes through its stale range
        with pytest.raises(ShardOwnershipLostError):
            wire_call(("127.0.0.1", cluster.hosts["host-0"]),
                      ("admin_stale_probe", domain_id, wf), timeout=10)

        # self-heal: real traffic re-acquires past the usurper and works
        drive_workflow(fe0, wf)
        ms = fe0.describe_workflow_execution(DOMAIN, wf)
        assert ms.execution_info.close_status == CloseStatus.Completed

    def test_killed_host_shards_are_stolen_and_served(self, cluster):
        """Pause host-1 (it stops heartbeating — the failure detector's
        view of a dead/partitioned host), watch host-0 steal its shards,
        then SIGKILL it and complete a workflow that lived there."""
        fe0 = cluster.frontend(0)
        owned_before = cluster.owned_shards()
        assert set(owned_before) == {"host-0", "host-1"}
        target_wf = wf_on_host(owned_before, "host-1")
        fe0.start_workflow_execution(DOMAIN, target_wf, "wiretype", TL)

        cluster.pause_host("host-1")
        deadline = time.monotonic() + 20
        stolen = False
        while time.monotonic() < deadline:
            owned = cluster.owned_shards().get("host-0", [])
            if set(owned_before["host-1"]).issubset(set(owned)):
                stolen = True
                break
            time.sleep(0.1)
        assert stolen, "host-0 never stole the paused host's shards"

        cluster.kill_host("host-1", signal.SIGKILL)
        # the stolen workflow completes through the survivor, over the wire
        drive_workflow(fe0, target_wf)
        ms = fe0.describe_workflow_execution(DOMAIN, target_wf)
        assert ms.execution_info.close_status == CloseStatus.Completed


class TestWireApiSurface:
    def test_new_apis_work_over_the_wire(self, cluster):
        """SignalWithStart, query visibility, count, domain update, and
        batch all cross the process boundary (pickled args/results over
        real sockets)."""
        fe = cluster.frontend(0)
        run = fe.signal_with_start_workflow_execution(
            DOMAIN, "wf-sws-wire", signal_name="go",
            workflow_type="orders", task_list=TL)
        assert run
        fe.update_domain(DOMAIN, description="wire-updated")
        assert fe.describe_domain(DOMAIN).description == "wire-updated"
        assert fe.count_workflow_executions(DOMAIN) >= 0
        # drive the decision so visibility records the start (host-1 was
        # SIGKILLed by the steal test earlier in this module: the survivor
        # serving everything IS the point)
        drive_workflow(fe, "wf-sws-wire")
        # visibility trails the async close-task pump: poll briefly
        deadline = time.monotonic() + 10
        hits = []
        while time.monotonic() < deadline:
            hits = fe.list_workflow_executions(
                DOMAIN,
                "WorkflowType = 'orders' AND CloseStatus = 'Completed'")
            if hits:
                break
            time.sleep(0.1)
        assert "wf-sws-wire" in [r.workflow_id for r in hits]
        # batch signal over the wire (no open matches left: zero targets)
        from cadence_tpu.engine.batcher import Batcher
        report = Batcher(fe, rps=100).run(
            DOMAIN, "WorkflowType = 'orders'", "signal", signal_name="x")
        assert report.total == 0


class TestWireAuth:
    """The wire trust boundary is enforced: every connection opens with a
    server nonce challenge; a peer that cannot answer
    HMAC(secret, nonce || ctx) — or, while the legacy fallback is allowed,
    the static preamble — is dropped before any frame is unpickled
    (advisor r4; replay hardening this round)."""

    @staticmethod
    def _recv_after_handshake(sock):
        """Bytes the server sends AFTER its 32-byte nonce challenge
        (b"" = the connection was dropped without a response frame)."""
        nonce = b""
        while len(nonce) < 32:
            chunk = sock.recv(32 - len(nonce))
            if not chunk:
                return b""
            nonce += chunk
        sock.settimeout(2)
        try:
            return sock.recv(1024)
        except (TimeoutError, OSError):
            return b""

    def test_unauthenticated_peer_is_rejected(self):
        import pickle
        import socket
        import struct
        import threading

        from cadence_tpu.engine.persistence import Stores
        from cadence_tpu.rpc.storeserver import StoreServer
        from cadence_tpu.rpc.wire import call

        server = StoreServer(("127.0.0.1", 0), Stores())
        threading.Thread(target=server.serve_forever, daemon=True).start()
        try:
            addr = ("127.0.0.1", server.server_address[1])
            # authenticated challenge-response path works
            assert call(addr, ("ping",)) == "pong"
            # raw connection ignoring the challenge, garbage response: a
            # pickle frame is never processed — dropped, no response frame
            with socket.create_connection(addr, timeout=5) as sock:
                body = b"garbage-no-hello"
                sock.sendall(struct.pack(">I", len(body)) + body)
                assert self._recv_after_handshake(sock) == b""
            # wrong secret: a forged 32-byte response + a well-formed
            # frame is dropped without a response
            with socket.create_connection(addr, timeout=5) as sock:
                sock.sendall(b"\x00" * 32)
                body = pickle.dumps(("ping",))
                sock.sendall(struct.pack(">I", len(body)) + body)
                assert self._recv_after_handshake(sock) == b""
            assert call(addr, ("ping",)) == "pong"
        finally:
            server.shutdown()

    def test_challenge_response_blocks_replay(self, monkeypatch):
        """A captured handshake response must be useless on the NEXT
        connection (fresh nonce); the static legacy preamble is accepted
        only while CADENCE_TPU_WIRE_ALLOW_STATIC permits it."""
        import pickle
        import socket
        import struct
        import threading

        from cadence_tpu.engine.persistence import Stores
        from cadence_tpu.rpc.storeserver import StoreServer
        from cadence_tpu.rpc.wire import _challenge_mac, _hello_mac, call

        server = StoreServer(("127.0.0.1", 0), Stores())
        threading.Thread(target=server.serve_forever, daemon=True).start()
        try:
            addr = ("127.0.0.1", server.server_address[1])
            body = pickle.dumps(("ping",))
            frame = struct.pack(">I", len(body)) + body
            # legacy static preamble: accepted under the default fallback
            with socket.create_connection(addr, timeout=5) as sock:
                sock.recv(32)  # a legacy client ignores the challenge
                sock.sendall(_hello_mac() + frame)
                kind, payload = pickle.loads(sock.recv(4096)[4:])
                assert (kind, payload) == ("ok", "pong")
            monkeypatch.setenv("CADENCE_TPU_WIRE_ALLOW_STATIC", "0")
            # replay: a valid response for connection A fails on B
            with socket.create_connection(addr, timeout=5) as first:
                nonce = first.recv(32)
                captured = _challenge_mac(nonce)
            with socket.create_connection(addr, timeout=5) as sock:
                sock.sendall(captured + frame)  # stale nonce's MAC
                assert self._recv_after_handshake(sock) == b""
            # legacy preamble: rejected once the fallback is disabled
            with socket.create_connection(addr, timeout=5) as sock:
                sock.sendall(_hello_mac() + frame)
                assert self._recv_after_handshake(sock) == b""
            # the real client still authenticates
            assert call(addr, ("ping",)) == "pong"
        finally:
            server.shutdown()
