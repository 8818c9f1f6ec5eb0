"""Open-loop walk queries served by ``WalkService`` over its TCP
front-end on loopback.

One client connection carries the load: a sender (this thread) writes
each submit at its due time, whatever the service is doing, and a
receiver thread polls finished walks back.  Every request is timed on
the client from its due time to the moment its path was received;
``query_p95_ms`` is the 95th percentile over every request due in the
window, and a request that is refused, fails or is unfinished when the
drain ends counts at the drain's end.

Set-up compiles what the window runs.  The service admits each tenant's
pending queries at once, and the program compiles its admission anew for
every number of queries admitted together; so set-up admits every size
from 1 up to the most requests of one program that the seeded schedule
makes due within ``admit_gap_s`` (the configuration's bound on the
time between two admissions of a tenant; ``bench/sweep.py`` shows each
tenant's largest admission beside it), in bursts over the same TCP
path.  A warm-up at
the cell's own load follows, so the window starts from a service in its
steady state.  Sizes admitted in the window that set-up never admitted
are counted (``new_admit_sizes``).
"""
from __future__ import annotations

import socket
import threading
import time

import numpy as np

import floor_bytes
import graphgen
import harness
import reference
import traffic


class LoadClient:
    """The open-loop client: submits by due time, polls walks back."""

    POLL_MAX = 256  # walks per poll frame (a deepwalk path is ~0.8 KB)

    def __init__(self, host: str, port: int, schedule: dict, t0: float):
        from repro.serving import transport as tp
        self.tp = tp
        self.sock = socket.create_connection((host, port), timeout=120)
        self.wlock = threading.Lock()
        self.sched = schedule
        self.t0 = t0  # host-clock time of due == 0
        n = schedule["due"].shape[0]
        self.sent = np.full(n, np.nan)
        self.recv = np.full(n, np.nan)
        self.status = np.full(n, "", dtype=object)
        self.walks = [None] * n
        self.ticket_of = {}
        self.done = 0  # requests terminal (walk received or refused)
        self.stop = threading.Event()
        self.error = None
        self.thread = threading.Thread(target=self._receive, daemon=True,
                                       name="bench-load-receiver")

    def _send(self, obj):
        with self.wlock:
            self.tp.send_frame(self.sock, obj)

    def _receive(self):
        tp = self.tp
        polls = 0
        try:
            while not self.stop.is_set():
                pid = f"p{polls}"
                polls += 1
                self._send({"op": tp.OP_POLL, "id": pid,
                            "max": self.POLL_MAX})
                got = 0
                while True:
                    frame = tp.recv_frame(self.sock)
                    if frame is None:
                        raise ConnectionError("front-end closed the socket")
                    now = time.perf_counter()
                    fid = str(frame.get("id"))
                    op = frame["op"]
                    if op == tp.OP_SUBMIT_OK:
                        self.ticket_of[int(frame["ticket"])] = int(fid[1:])
                    elif op == tp.OP_ERROR and fid.startswith("s"):
                        i = int(fid[1:])
                        self.status[i] = frame.get("code", "error")
                        self.recv[i] = now
                        self.done += 1
                    elif op == tp.OP_WALKS and fid == pid:
                        for d in frame["walks"]:
                            i = self.ticket_of.pop(int(d["ticket"]))
                            w = tp.walk_from_wire(d)
                            self.walks[i] = w
                            self.status[i] = w.status
                            self.recv[i] = now
                            self.done += 1
                        got = len(frame["walks"])
                        break
                    elif op == tp.OP_ERROR:
                        raise RuntimeError(f"front-end error: {frame}")
                if got < self.POLL_MAX:
                    time.sleep(0.002)
        except BaseException as e:  # reported by the sender thread
            self.error = e

    def run(self, until: float) -> None:
        """Send every request by its due time, then wait until every one
        is terminal or the host clock passes ``until``."""
        tp = self.tp
        self.thread.start()
        due = self.sched["due"]
        names = self.sched["programs"]
        for i in range(due.shape[0]):
            wait = self.t0 + due[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            self._send({"op": tp.OP_SUBMIT, "id": f"s{i}",
                        "start": int(self.sched["start"][i]),
                        "program": names[int(self.sched["program"][i])],
                        "priority": 0})
            self.sent[i] = time.perf_counter()
            if self.error is not None:
                raise self.error
        while self.done < due.shape[0] and time.perf_counter() < until:
            if self.error is not None:
                raise self.error
            time.sleep(0.01)

    def close(self):
        self.stop.set()
        self.thread.join(timeout=30)
        self.sock.close()


def _snapshot(fe, programs):
    """Each tenant's (epochs run, scheduler counters) right now."""
    with fe.lock:
        return {name: (t.epochs_run, dict(t.sched.totals)) for name, t in
                ((n, fe.service.tenant(n)) for n in programs)}


def build(ctx: harness.Context):
    """The graph's host arrays, the service with its tenants built, and
    its front-end started: (indptr, indices, h, service, frontend)."""
    from repro.core import EngineConfig
    from repro.serving import ServiceConfig, WalkFrontend, WalkService
    from repro.serving.frontend import FrontendConfig

    cfg = ctx.config
    indptr, indices, h = graphgen.make_graph(cfg["graph"], ctx.seed)
    sc = cfg["service"]
    svc = WalkService(
        harness.device_graph(indptr, indices, h),
        ServiceConfig(slots=sc["slots"], epoch_len=sc["epoch_len"],
                      fairness=sc["fairness"], max_pending=sc["max_pending"],
                      seed=ctx.sub_seed("walks"), devices=cfg["devices"]),
        EngineConfig(method=cfg["engine"]["method"]),
        programs={n: harness.program(s)
                  for n, s in cfg["programs"].items()})
    for name in cfg["programs"]:
        svc.tenant(name)
    fe = WalkFrontend(svc, FrontendConfig(
        client_buffer=cfg["frontend"]["client_buffer"]))
    fe.start()
    return indptr, indices, h, svc, fe


def record_admissions(svc, names):
    """Wrap each tenant scheduler's ``admit`` to record the sizes it
    admits: {tenant: set of sizes}, {tenant: [(time, size), ...]}."""
    sizes = {n: set() for n in names}
    log = {n: [] for n in names}
    for name in names:
        sched = svc.tenant(name).sched
        real = sched.admit

        def admit(query_ids, starts, _real=real, _name=name):
            k = len(query_ids)
            sizes[_name].add(k)
            log[_name].append((time.perf_counter(), k))
            return _real(query_ids, starts)

        sched.admit = admit
    return sizes, log


def largest_admission(sched: dict, gap_s: float) -> int:
    """The most requests of one program the schedule makes due within
    any ``gap_s`` seconds: the largest admission it can cause while the
    service admits at least once every ``gap_s``."""
    best = 0
    for p in range(len(sched["programs"])):
        t = np.sort(sched["due"][sched["program"] == p])
        if t.size:
            j = np.searchsorted(t, t + gap_s, side="right")
            best = max(best, int((j - np.arange(t.size)).max()))
    return best


def warm_admissions(ctx: harness.Context, fe, nodes, sizes: dict,
                    k_max: int, rounds: int = 4) -> tuple:
    """Admit every size 1..``k_max`` in every tenant: bursts of k
    requests of each program at once, each answered before the next.  A
    burst the service splits across two admissions is sent again, up to
    ``rounds`` times, while a round still admits a new size; a burst
    left unanswered ends the warm-up (it counts as missing).  Returns
    (sizes still not admitted, requests not answered)."""
    names = sorted(sizes)
    rng = traffic.rng_for(ctx.seed, "warm")
    unanswered = 0

    def todo():
        return [k for k in range(1, k_max + 1)
                if any(k not in sizes[n] for n in names)]

    for _ in range(rounds):
        left = todo()
        for k in left:
            burst = {"due": np.zeros(k * len(names)),
                     "start": rng.choice(nodes, k * len(names))
                     .astype(np.int32),
                     "program": np.repeat(np.arange(len(names)), k)
                     .astype(np.int32),
                     "programs": names}
            client = LoadClient(*fe.address, burst, time.perf_counter())
            try:
                client.run(until=time.perf_counter()
                           + ctx.config["prewarm_timeout_s"])
            finally:
                client.close()
            lost = int(sum(s != "completed" for s in client.status))
            if lost:  # the run is not correct: warming further is moot
                return todo(), unanswered + lost
        if todo() == left:
            break
    return todo(), unanswered


def prewarm(ctx: harness.Context, fe, nodes) -> int:
    """A few requests of every program, answered before the timed
    schedule starts: both tenants' epochs are compiled by then.  Returns
    how many were not answered."""
    pre = traffic.open_loop(dict(ctx.traffic, arrivals="poisson",
                                 rate_qps=8), nodes, ctx.seed + 1,
                            0.0, 1.0)
    warm = LoadClient(*fe.address, pre, time.perf_counter())
    try:
        warm.run(until=time.perf_counter()
                 + ctx.config["prewarm_timeout_s"])
    finally:
        warm.close()
    return int(sum(s != "completed" for s in warm.status))


def run(ctx: harness.Context) -> harness.Run:
    import jax

    cfg, mix = ctx.config, ctx.traffic
    specs = cfg["programs"]
    sc = cfg["service"]
    indptr, indices, h, svc, fe = build(ctx)
    nodes = graphgen.walk_starts(indptr)
    host, port = fe.address
    sizes, admits = record_admissions(svc, sorted(specs))
    try:
        unanswered = prewarm(ctx, fe, nodes)
        warmup = float(cfg["warmup_s"])
        sched = traffic.open_loop(mix, nodes, ctx.seed, -warmup,
                                  ctx.seconds)
        # a tenant admits at most its slots at once
        k_max = min(largest_admission(sched, float(cfg["admit_gap_s"])),
                    int(sc["slots"]))
        unwarmed, lost = warm_admissions(ctx, fe, nodes, sizes, k_max)
        unanswered += lost
        warmed = {n: set(v) for n, v in sizes.items()}
        t_w0 = time.perf_counter() + warmup + 0.05
        client = LoadClient(host, port, sched, t_w0)
        win = harness.Window(ctx.trace, ctx.compiles)
        in_win = sched["due"] >= 0
        # the warm-up is part of set-up; the window opens at due 0
        marks = {}

        def open_window():
            marks["setup_s"] = time.perf_counter() - ctx.t_start
            marks["before"] = _snapshot(fe, specs)

        def send():
            try:
                client.run(until=t_w0 + ctx.seconds + float(cfg["drain_s"]))
            except BaseException as e:  # re-raised below
                client.error = e

        client_thread = threading.Thread(target=send, daemon=True,
                                         name="bench-load-sender")
        client_thread.start()
        time.sleep(max(t_w0 - time.perf_counter(), 0))
        open_window()
        with win.measure():
            time.sleep(max(t_w0 + ctx.seconds - time.perf_counter(), 0))
        marks["after"] = _snapshot(fe, specs)
        t_close = time.perf_counter()
        client_thread.join()
        if client.error is not None:
            raise client.error
        t_end = time.perf_counter()
        client.close()
        mem = harness.memory_peak([jax.devices()[0]])
    finally:
        fe.drain(timeout=5)
        fe.stop()
    platform = jax.devices()[0].platform
    reduced = win.reduce(kernels=cfg["kernels"], platform=platform)
    del svc, fe

    # ------------------------------------------------------ the window
    idx = np.nonzero(in_win)[0]
    due_abs = t_w0 + sched["due"][idx]
    ok = np.asarray([client.status[i] == "completed" for i in idx], bool)
    recv = np.where(ok, client.recv[idx], t_end)
    latency = recv - due_abs
    n = idx.size
    p95 = float(np.sort(latency)[max(int(np.ceil(0.95 * n)) - 1, 0)]) \
        if n else float("nan")
    walks = [client.walks[i] for i in idx[ok]]
    failed = int(n - ok.sum())
    wait = np.asarray([w.wait for w in walks])
    svc_lat = np.asarray([w.latency for w in walks])
    tcp = (client.recv[idx[ok]] - client.sent[idx[ok]]) - svc_lat
    lag = client.sent[idx] - due_abs

    # ------------------------------------------------- the comparison
    # every answer of the run, warm-up included, is compared: a request
    # that never comes back counts wherever it was sent
    done = np.nonzero(client.status == "completed")[0]
    missing = unanswered + int(client.status.size - done.size)
    walks = [client.walks[i] for i in done]
    g = reference.Graph(indptr, indices, h)
    lim = cfg["limits"]
    rng = traffic.rng_for(ctx.seed, "check")
    names = sched["programs"]
    prog = sched["program"][done]
    starts = sched["start"][done]
    bad_hops = bad_len = ctl_len = 0
    hop_sets = []
    for p, name in enumerate(names):
        mine = prog == p
        if not mine.any():
            continue
        L = int(specs[name]["walk_len"])
        paths = np.full((int(mine.sum()), L + 1), -1, np.int64)
        for r, w in enumerate(w for w, m in zip(walks, mine) if m):
            paths[r, :min(w.path.size, L + 1)] = w.path[:L + 1]
        bad_hops += reference.form_errors(g, starts[mine], paths)
        bad_len += reference.length_errors(
            specs[name], g, paths, np.ones(paths.shape[0], bool))
        if ctx.control:
            ctl_len += reference.control_stop_disagreements(
                specs[name], g, paths)
        _, _, prev, cur, nxt = reference.walk_hops(paths)
        hop_sets.append((specs[name], prev, cur, nxt))
    total = sum(s[1].size for s in hop_sets)
    u, u_ctl = [], []
    for spec, prev, cur, nxt in hop_sets:
        take = int(round(cfg["pit_sample"] * prev.size / max(total, 1)))
        if prev.size > take:
            pick = np.sort(rng.choice(prev.size, take, replace=False))
            prev, cur, nxt = prev[pick], cur[pick], nxt[pick]
        u.append(reference.pit_values(spec, g, prev, cur, nxt, rng))
        if ctx.control:
            x = reference.control_draws(spec, g, prev, cur, rng)
            u_ctl.append(reference.pit_values(spec, g, prev, cur, x, rng))
    u = np.concatenate(u) if u else np.zeros(0)
    checks = {
        "bad_hops": (bad_hops, lim["bad_hops"]),
        "bad_lengths": (bad_len, lim["bad_lengths"]),
        "missing": (missing, lim["missing"]),
        "pit_ks": (reference.ks_sqrt_n(u), lim["pit_ks"]),
    }
    if ctx.control:
        checks["control.bad_lengths"] = (ctl_len, lim["bad_lengths"])
        checks["control.pit_ks"] = (
            reference.ks_sqrt_n(np.concatenate(u_ctl)), lim["pit_ks"])

    before, after = marks["before"], marks["after"]
    scan_steps = sum((after[k][0] - before[k][0]) for k in after) \
        * int(sc["epoch_len"])
    live = {k: after[k][1]["live"] - before[k][1]["live"] for k in after}
    fbytes = sum(floor_bytes.hop_floor_bytes(specs[k]["kind"], hops=v)
                 for k, v in live.items())
    record = {
        "trace": reduced,
        "scan_steps": scan_steps,
        "live": sum(live.values()),
        "floor_bytes": fbytes,
        "queue_wait_s": wait,
        "tcp_overhead_s": tcp,
        "send_lag_s": lag,
        "memory_peak_bytes": mem,
        "peaks": ctx.peaks,
        "compiles_in_window": win.compiles_in_window,
        "admit_sizes_warmed": k_max,
        "admit_sizes_unwarmed": unwarmed,
        "new_admit_sizes": sorted({k for n, log in admits.items()
                                   for t, k in log
                                   if win.t0 <= t <= t_close
                                   and k not in warmed[n]}),
    }
    return harness.Run(
        setup_s=marks["setup_s"],
        e2e={"query_p95_ms": 1e3 * p95},
        attempted=n, failed=failed, checks=checks, record=record,
        memory_peak_bytes=mem, device_count=1)
