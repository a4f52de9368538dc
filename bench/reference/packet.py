"""Plain per-packet simulation of one training iteration, with HPCC.

The semantics every packet-level backend is held to: FIFO ports with a
per-port buffer and ECN marking above ``ecn_k`` bytes of backlog, paced
senders limited by a congestion window, per-hop INT telemetry carried to
the sender, HPCC's window rule (Li et al., SIGCOMM'19, Algorithm 1) on
every ACK, losses learned one smoothed RTT later, and a timeout that counts
everything in flight lost when no ACK came for ``max(10 srtt, 20 sample
intervals)``.  Phases launch as their dependencies finish.

Events are ``(time, seq, kind, payload)`` in a heap; ``seq`` is the order of
scheduling, so ties resolve the same way in every run.  Nothing is skipped,
parked or approximated.
"""
from __future__ import annotations

import gc
import heapq

from bench.reference.deployment import Fabric, Phase

START, SEND, ARRIVE, ACK, LOSS, SAMPLE, CALL = range(7)


class HPCC:
    """Sender state of one flow under HPCC."""

    __slots__ = ("line_rate", "base_rtt", "r", "w", "srtt", "eta", "w_ref",
                 "w_ai", "max_stage", "stage", "u", "last_ack_t", "acked",
                 "update_seq", "w_cap", "mtu")

    def __init__(self, line_rate: float, base_rtt: float, mtu: float,
                 eta: float, max_stage: int) -> None:
        self.line_rate = line_rate
        self.base_rtt = base_rtt
        self.mtu = mtu
        self.r = line_rate
        self.w = line_rate * base_rtt
        self.srtt = base_rtt
        self.eta = eta
        self.w_ref = self.w
        self.w_ai = mtu / 2
        self.max_stage = max_stage
        self.stage = 0
        self.u = eta
        self.last_ack_t = 0.0
        self.acked = 0.0
        self.update_seq = 0.0
        self.w_cap = 1.05 * line_rate * base_rtt + max_stage * self.w_ai

    def on_ack(self, now: float, acked: float, rtt: float, u: float) -> None:
        self.srtt = 0.875 * self.srtt + 0.125 * rtt
        self.acked += acked
        tau = min(max(now - self.last_ack_t, 1e-12) / self.base_rtt, 1.0)
        self.last_ack_t = now
        self.u = (1 - tau) * self.u + tau * u
        update_wc = self.acked >= self.update_seq
        if self.u >= self.eta or self.stage >= self.max_stage:
            w = self.w_ref / max(self.u / self.eta, 0.2) + self.w_ai
            if update_wc:
                self.stage = 0
        else:
            w = self.w_ref + self.w_ai
            if update_wc:
                self.stage += 1
        self.w = w = min(max(w, self.mtu), self.w_cap)
        if update_wc:
            self.w_ref = w
            self.update_seq = self.acked + w
        self.r = w / max(self.srtt, 1e-9)


class FlowState:
    __slots__ = ("fid", "size", "path", "ack_delay", "cca", "started", "done",
                 "start_t", "sent", "delivered", "inflight", "retx",
                 "blocked", "send_pending", "last_ack_t", "epoch",
                 "last_sample_t", "int_prev", "phase")

    def __init__(self, fid, size, path, ack_delay, cca, phase) -> None:
        self.fid = fid
        self.size = size
        self.path = path
        self.ack_delay = ack_delay
        self.cca = cca
        self.phase = phase
        self.started = self.done = self.blocked = self.send_pending = False
        self.start_t = self.sent = self.delivered = 0.0
        self.inflight = self.retx = self.last_ack_t = self.last_sample_t = 0.0
        self.epoch = 0
        self.int_prev: dict[int, tuple[float, float, float]] = {}


class PacketReference:
    """Simulates ``phases`` on ``fabric``; :meth:`run` returns per-flow
    completion times, the iteration time and the number of events."""

    def __init__(self, fabric: Fabric, phases: list[Phase], sim: dict,
                 hpcc: dict) -> None:
        self.fabric = fabric
        self.phases = phases
        self.mtu = sim["mtu"]
        self.ecn_k = sim["ecn_k"]
        self.buffer = sim["buffer_bytes"]
        self.sample_interval = max(sim["min_sample_interval"],
                                   sim["sample_mtus"] * self.mtu
                                   / float(fabric.bw.min()))
        self.hpcc = hpcc
        self.bw = [float(v) for v in fabric.bw]
        self.delay = [float(v) for v in fabric.delay]
        self.busy = [0.0] * len(self.bw)
        self.txbytes = [0.0] * len(self.bw)
        self.flows: dict[int, FlowState] = {}
        self.fct: dict[int, float] = {}
        self.heap: list = []
        self.seq = 0
        self.now = 0.0
        self.events = 0
        self.sample_pending = False
        self.left = [len(p.flows) for p in phases]
        self.waiting = [len(set(p.deps)) for p in phases]
        self.done_t: list[float | None] = [None] * len(phases)
        self.launched = [False] * len(phases)
        self.dependents: list[list[int]] = [[] for _ in phases]
        for j, p in enumerate(phases):
            for d in sorted(set(p.deps)):
                self.dependents[d].append(j)

    # -- scheduling ------------------------------------------------------ #
    def at(self, t: float, kind: int, *payload) -> None:
        heapq.heappush(self.heap, (max(t, self.now), self.seq, kind, payload))
        self.seq += 1

    def launch(self, i: int, t: float) -> None:
        if self.launched[i]:
            return
        self.launched[i] = True
        p = self.phases[i]
        start = t + p.compute
        if not p.flows:
            self.at(start, CALL, i)
            return
        for fl in p.flows:
            path = self.fabric.route(fl.src, fl.dst, fl.fid)
            line = min(self.bw[q] for q in path)
            prop = float(self.fabric.delay[path].sum())
            base_rtt = 2 * prop + (len(path) + 1) * self.mtu / line
            cca = HPCC(line, base_rtt, self.mtu, self.hpcc["eta"],
                       self.hpcc["max_stage"])
            self.flows[fl.fid] = FlowState(fl.fid, fl.size, path, prop, cca, i)
            self.at(max(start, self.now), START, fl.fid)

    def complete(self, i: int, t: float) -> None:
        self.done_t[i] = t
        for j in self.dependents[i]:
            self.waiting[j] -= 1
            if self.waiting[j] == 0:
                self.launch(j, max(self.done_t[d]
                                   for d in set(self.phases[j].deps)))

    def finish(self, f: FlowState, t: float) -> None:
        f.done = True
        f.delivered = f.size
        self.fct[f.fid] = t - f.start_t
        self.left[f.phase] -= 1
        if self.left[f.phase] == 0:
            self.complete(f.phase, t)

    # -- events ---------------------------------------------------------- #
    def start(self, t: float, fids: list[int]) -> None:
        for fid in fids:
            f = self.flows[fid]
            f.started = True
            f.start_t = f.last_sample_t = f.last_ack_t = t
        for fid in fids:
            f = self.flows[fid]
            if not f.send_pending and not f.done:
                f.send_pending = True
                self.at(t, SEND, fid, f.epoch)
        self.ensure_sampler(t)

    def send(self, t: float, f: FlowState) -> None:
        f.send_pending = False
        if f.done or not f.started:
            return
        want = f.retx if f.retx > 0 else min(self.mtu, f.size - f.sent)
        if want <= 0:
            return
        # one packet may always be in flight, whatever the window
        if f.inflight > 0 and f.inflight + self.mtu > max(f.cca.w, self.mtu):
            f.blocked = True
            return
        pkt = min(self.mtu, want)
        if f.retx > 0:
            f.retx -= pkt
        else:
            f.sent += pkt
        f.inflight += pkt
        self.at(t, ARRIVE, f.fid, 0, pkt, t, False, (), f.epoch)
        if f.sent < f.size or f.retx > 0:
            f.send_pending = True
            self.at(t + pkt / max(f.cca.r, self.mtu), SEND, f.fid, f.epoch)

    def arrive(self, t, f, hop, pkt, t_sent, ecn, telemetry) -> None:
        if hop >= len(f.path):
            self.at(t + f.ack_delay, ACK, f.fid, pkt, t_sent, ecn, telemetry,
                    f.epoch)
            return
        port = f.path[hop]
        bw = self.bw[port]
        depart = max(self.busy[port], t)
        backlog = (depart - t) * bw
        if backlog + pkt > self.buffer:
            self.at(t + f.cca.srtt, LOSS, f.fid, pkt, f.epoch)
            return
        ecn = ecn or backlog > self.ecn_k
        tx_end = depart + pkt / bw
        self.busy[port] = tx_end
        self.txbytes[port] += pkt
        telemetry = telemetry + ((port, self.txbytes[port], tx_end, backlog),)
        self.at(tx_end + self.delay[port], ARRIVE, f.fid, hop + 1, pkt,
                t_sent, ecn, telemetry, f.epoch)

    def ack(self, t, f, pkt, t_sent, telemetry) -> None:
        f.inflight = max(f.inflight - pkt, 0.0)
        f.delivered += pkt
        f.last_ack_t = t
        base_rtt = f.cca.base_rtt
        u_max = 0.0
        for port, txb, ts, qlen in telemetry:
            bw = self.bw[port]
            prev = f.int_prev.get(port)
            if prev is not None and ts > prev[1] + 1e-12:
                u = (min(qlen, prev[2]) / (bw * base_rtt)
                     + (txb - prev[0]) / ((ts - prev[1]) * bw))
            else:
                u = 0.95 + qlen / (bw * base_rtt)
            f.int_prev[port] = (txb, ts, qlen)
            u_max = max(u_max, u)
        f.cca.on_ack(t, pkt, t - t_sent, u_max)
        if f.delivered >= f.size:
            self.finish(f, t)
            return
        if (f.blocked or not f.send_pending) and (f.sent < f.size
                                                  or f.retx > 0):
            f.blocked = False
            f.send_pending = True
            self.at(t, SEND, f.fid, f.epoch)

    def loss(self, t, f, pkt) -> None:
        f.inflight = max(0.0, f.inflight - pkt)
        f.retx += pkt
        f.cca.on_ack(t, 0.0, f.cca.srtt * 2, 2.0)   # as severe congestion
        if not f.send_pending:
            f.send_pending = True
            self.at(t, SEND, f.fid, f.epoch)

    def ensure_sampler(self, t: float) -> None:
        if not self.sample_pending and any(
                f.started and not f.done for f in self.flows.values()):
            self.sample_pending = True
            self.at(t + self.sample_interval, SAMPLE)

    def sample(self, t: float) -> None:
        self.sample_pending = False
        limit = 20 * self.sample_interval
        for f in self.flows.values():
            if not f.started or f.done or t - f.last_sample_t <= 0:
                continue
            f.last_sample_t = t
            if f.inflight > 0 and t - f.last_ack_t > max(10 * f.cca.srtt,
                                                         limit):
                # timeout: what is in flight is lost, its events are void
                f.retx += f.inflight
                f.inflight = 0.0
                f.epoch += 1
                f.last_ack_t = t
                f.blocked = False
                f.send_pending = True
                self.at(t, SEND, f.fid, f.epoch)
        self.ensure_sampler(t)

    # -- loop ------------------------------------------------------------ #
    def run(self) -> dict:
        for i, p in enumerate(self.phases):
            if not p.deps:
                self.launch(i, 0.0)
        # millions of short-lived event tuples and none can form a cycle:
        # the cyclic collector would only pause the loop
        gc_was_on = gc.isenabled()
        gc.disable()
        try:
            self._loop()
        finally:
            if gc_was_on:
                gc.enable()
        if any(d is None for d in self.done_t):
            raise RuntimeError("reference: the iteration did not finish")
        return {"fct": self.fct, "iteration_time": max(self.done_t),
                "events": self.events}

    def _loop(self) -> None:
        heap = self.heap
        while heap:
            t, _, kind, payload = heapq.heappop(heap)
            self.now = t
            self.events += 1
            if kind == START:
                fids = [payload[0]]
                while heap and heap[0][0] == t and heap[0][2] == START:
                    fids.append(heapq.heappop(heap)[3][0])
                    self.events += 1
                self.start(t, fids)
                continue
            if kind == SAMPLE:
                self.sample(t)
                continue
            if kind == CALL:
                self.complete(payload[0], t)
                continue
            f = self.flows[payload[0]]
            if payload[-1] != f.epoch or f.done and kind != SEND:
                continue
            if kind == ARRIVE:
                self.arrive(t, f, *payload[1:-1])
            elif kind == SEND:
                self.send(t, f)
            elif kind == ACK:
                self.ack(t, f, payload[1], payload[2], payload[4])
            elif kind == LOSS:
                self.loss(t, f, payload[1])
