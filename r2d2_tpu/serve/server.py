"""Central policy inference server: micro-batched forwards over a
server-held state cache (ISSUE 13 tentpole; SEED arXiv 1910.03552,
CPU/GPU placement study arXiv 2012.04210).

One loop owns the resident params and the ``StateCache``; requests from
any transport rung (serve/transport.py) land in one inbox and the
micro-batcher folds them into ONE jitted gather-state → forward →
scatter-state dispatch under a latency deadline:

    dispatch when the batch FILLS (``serve.max_batch``)
    OR the OLDEST pending request ages out (``serve.deadline_ms``)

Batches are padded up to power-of-two buckets (all pre-compiled at start,
the ingest stager's AOT recipe) so fill jitter never retraces. The
forward is the ONE shared acting forward (``actor.policy.make_forward_fn``
— the same program local policies run, which is what makes local-vs-served
action parity exact). Weights sync from the existing weight service
(runtime/weights.py): the server polls its reader on an interval and
stamps every reply with the adopted publish count, so the staleness
accounting (ISSUE 5) stays live for served actors.

Telemetry rides the canonical stages (``serve/enqueue``,
``serve/batch_wait``, ``serve/forward``, ``serve/reply``) plus the
``ServingStats`` aggregator: request-latency and batch-fill histograms on
the shared 64-bucket layout, lease/churn counters — the periodic record's
``serving`` block and the ``serve_*`` alert rules' input.
"""

import logging
import queue
import threading
import time
from typing import Callable, List, Optional, Tuple

import numpy as np

from r2d2_tpu.serve.state_cache import MisroutedClient
from r2d2_tpu.serve.transport import (KIND_DISCONNECT, KIND_STEP, Reply,
                                      Request, STATUS_EXPIRED,
                                      STATUS_MISROUTED, STATUS_OK,
                                      STATUS_RETRY)


def serve_buckets(max_batch: int) -> List[int]:
    """Power-of-two dispatch widths up to ``max_batch`` (inclusive, as
    its own bucket when not a power of two) — the stager's pow2 recipe,
    so every possible fill compiles at server start, never mid-run."""
    out = []
    b = 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return out


def collect_batch(inbox: "queue.Queue", first, max_batch: int,
                  deadline_s: float, expected: Optional[int] = None) -> list:
    """The micro-batch fill loop: starting from ``first`` (already
    popped), keep pulling until the batch fills or the OLDEST request
    (= first) ages past ``deadline_s`` from its arrival stamp.

    ``expected`` is the early-dispatch target: the number of clients
    that can possibly have a request outstanding (blocking clients hold
    at most one in flight, so once every connected client is
    represented, waiting out the deadline is pure added latency — the
    measured cost was a full deadline per dispatch at steady state).
    Reaching it stops the WAIT but still drains any immediately-pending
    backlog up to ``max_batch``.

    The deadline bounds WAITING only: when ``first`` is already past it
    (it aged in the queue while the server was mid-forward), the
    immediately-pending backlog is still drained before dispatch —
    otherwise a backlogged server degenerates into batch-1 dispatches
    of stale requests, each one aging the rest of the queue further
    (measured as fill ~1 at 4x the per-request latency under a 4-deep
    backlog). Module-level so the deadline/fill semantics unit-test
    without a server."""
    batch = [first]
    deadline = first[0].t_recv + deadline_s
    target = (max_batch if expected is None
              else min(max_batch, max(int(expected), 1)))
    while len(batch) < max_batch:
        remaining = deadline - time.monotonic()
        if len(batch) >= target or remaining <= 0:
            try:
                batch.append(inbox.get_nowait())
                continue           # burst backlog: take it, don't wait
            except queue.Empty:
                break
        try:
            batch.append(inbox.get(timeout=remaining))
        except queue.Empty:
            break
    return batch


class ServingStats:
    """Thread-safe serving aggregator shared by the server loop and (in
    in-proc mode) the clients: request-latency and batch-fill histograms
    on the shared 64-bucket layout (telemetry/histogram.py — mergeable,
    percentile-summarized), dispatch-cause counters, and client-churn
    accounting. ``interval_block`` consumes the interval (the
    TrainMetrics provider contract); ``disconnects``/``timeouts`` stay
    CUMULATIVE inside the block so the counter-kind alert rules
    (``serve_client_churn``) get their edge semantics."""

    def __init__(self):
        from r2d2_tpu.telemetry.histogram import NBUCKETS
        self._lock = threading.Lock()
        self._nb = NBUCKETS
        self._lat = np.zeros(NBUCKETS, np.int64)
        self._fill = np.zeros(NBUCKETS, np.int64)
        self._fill_sum = 0
        self._batches = 0
        self._full = 0
        self._deadline = 0
        self._starved = 0
        self._requests = 0
        self._replies = 0
        self._expired = 0
        self.timeouts_total = 0
        self.disconnects_total = 0
        self._connects = 0
        self._reconnects = 0
        self._evictions = 0
        self.active_clients = 0
        # -- admission control / routing (ISSUE 17) -- the ``admission``
        # sub-block only exists when the fleet features are ON
        # (admission_enabled), which is what keeps the default
        # single-server record byte-identical (kill-switch contract).
        self.admission_enabled = False
        self._shed = 0
        self._misrouted = 0
        self._adm_lat = np.zeros(NBUCKETS, np.int64)
        # -- distributed tracing (ISSUE 19) -- a ServeTrace is attached
        # when telemetry.tracing_enabled; the ``trace`` sub-block exists
        # only then (same presence gating as ``admission``).
        self.trace = None

    # -- feed points --

    def on_request_latency(self, seconds: float) -> None:
        """One client-visible request completion (or timed-out attempt —
        the wait was experienced either way; during a server outage these
        attempts ARE the latency signal the SLO rule fires on)."""
        from r2d2_tpu.telemetry.histogram import bucket_index
        with self._lock:
            self._lat[bucket_index(seconds)] += 1

    def on_timeout(self, seconds: float) -> None:
        with self._lock:
            self.timeouts_total += 1
        self.on_request_latency(seconds)

    def on_batch(self, fill: int, hit_full: bool, hit_deadline: bool,
                 starved: bool) -> None:
        from r2d2_tpu.telemetry.histogram import value_counts_np
        counts = value_counts_np(np.asarray([fill], np.float64))
        with self._lock:
            self._fill += counts
            self._fill_sum += fill
            self._batches += 1
            self._full += int(hit_full)
            self._deadline += int(hit_deadline)
            self._starved += int(starved)

    def on_requests(self, n: int = 1) -> None:
        with self._lock:
            self._requests += n

    def on_replies(self, n: int = 1) -> None:
        with self._lock:
            self._replies += n

    def on_expired(self, n: int = 1) -> None:
        with self._lock:
            self._expired += n

    def on_shed(self, n: int = 1) -> None:
        """Requests rejected at the queue-depth bound (STATUS_RETRY) —
        they count as requests seen but never reach a dispatch."""
        with self._lock:
            self._shed += n
            self._requests += n

    def on_misrouted(self, n: int = 1) -> None:
        """Requests aimed at a server that does not own the client's
        shard (stale routing map) — bounced with the current map."""
        with self._lock:
            self._misrouted += n

    def on_admitted_latency(self, seconds: float) -> None:
        """Server-side receive→reply latency of an ADMITTED request —
        the brownout contract's p99 (shed requests never enter it)."""
        from r2d2_tpu.telemetry.histogram import bucket_index
        with self._lock:
            self._adm_lat[bucket_index(seconds)] += 1

    def on_clients(self, connects: int = 0, reconnects: int = 0,
                   disconnects: int = 0, evictions: int = 0) -> None:
        with self._lock:
            self._connects += connects
            self._reconnects += reconnects
            self.disconnects_total += disconnects
            self._evictions += evictions

    # -- emission --

    def interval_block(self, deadline_ms: Optional[float] = None,
                       max_batch: Optional[int] = None) -> Optional[dict]:
        """The periodic record's ``serving`` block; consumes the
        interval's histograms/counters. None when the interval saw no
        serving traffic at all (the block is then omitted — consumers
        key on presence, like every other pillar block)."""
        from r2d2_tpu.telemetry.histogram import summarize, value_summary
        with self._lock:
            if (self._requests == 0 and self._batches == 0
                    and not self._lat.any()):
                return None
            lat = summarize(self._lat)
            fill = value_summary(self._fill)
            block = {
                "requests": self._requests,
                "replies": self._replies,
                "expired": self._expired,
                "timeouts": self.timeouts_total,       # cumulative
                "latency": lat,
                "batch": {
                    "count": self._batches,
                    "fill_mean": (round(self._fill_sum / self._batches, 2)
                                  if self._batches else None),
                    "fill_p50": fill.get("p50") if fill else None,
                    "fill_p99": fill.get("p99") if fill else None,
                    "full_frac": (round(self._full / self._batches, 3)
                                  if self._batches else None),
                    "deadline_frac": (round(self._deadline / self._batches,
                                            3) if self._batches else None),
                    "starved_frac": (round(self._starved / self._batches, 3)
                                     if self._batches else None),
                },
                "clients": {
                    "active": self.active_clients,
                    "connects": self._connects,
                    "reconnects": self._reconnects,
                    "disconnects": self.disconnects_total,  # cumulative
                    "evictions": self._evictions,
                },
            }
            if deadline_ms is not None:
                block["deadline_ms"] = deadline_ms
            if max_batch is not None:
                block["max_batch"] = max_batch
            if self.admission_enabled:
                adm = summarize(self._adm_lat)
                block["admission"] = {
                    "shed": self._shed,
                    "shed_frac": (round(self._shed / self._requests, 3)
                                  if self._requests else 0.0),
                    "misrouted": self._misrouted,
                    "admitted_latency": adm,
                }
            if self.trace is not None:
                tr = self.trace.interval_block()
                if tr is not None:
                    block["trace"] = tr
            self._lat[:] = 0
            self._fill[:] = 0
            self._fill_sum = 0
            self._batches = self._full = self._deadline = self._starved = 0
            self._requests = self._replies = self._expired = 0
            self._connects = self._reconnects = self._evictions = 0
            self._shed = self._misrouted = 0
            self._adm_lat[:] = 0
        return block


class PolicyServer:
    """The server loop. Construction pins the params and (by default)
    pre-compiles every dispatch bucket; ``start()`` spawns the loop
    thread; ``stop()`` winds it down. The inbox (an ``InprocEndpoint``)
    and any shm/socket transports are EXTERNAL and survive a server
    restart — the chaos drill's server-kill/restart replaces only this
    object.

    ``weight_poll``/``weight_version``: the weight-service reader pair
    (e.g. ``lambda: store.poll("serve")`` + ``lambda:
    store.reader_version("serve")``, or a ``WeightSubscriber``'s
    ``poll``/``publish_count``). ``client_timed=True`` means in-proc
    clients feed the latency histogram themselves (round-trip including
    queueing and retries); the server then skips its own receive→reply
    observation so requests aren't double-counted."""

    def __init__(self, cfg, net, params, *, endpoint,
                 weight_poll: Optional[Callable] = None,
                 weight_version: Optional[Callable[[], int]] = None,
                 copy_updates: bool = True,
                 stats: Optional[ServingStats] = None,
                 telemetry=None, client_timed: bool = False,
                 warmup: Optional[bool] = None, quant_stats=None,
                 cache=None, server_id: int = 0, shard_map=None,
                 queue_depth_bound: Optional[int] = None,
                 device_index: int = 0, forward_fn=None,
                 local_stats: Optional[ServingStats] = None):
        import jax

        from r2d2_tpu.actor.policy import (_force_f32, _pin_params,
                                           make_forward_fn)
        from r2d2_tpu.models.network import (is_quant_bundle,
                                             make_inference_bundle)
        from r2d2_tpu.telemetry import NULL_TELEMETRY
        sv = cfg.serve
        self.cfg = cfg
        self.max_batch = sv.max_batch
        self.deadline_s = sv.deadline_ms / 1e3
        self.ttl_s = sv.request_ttl_s
        self._weight_poll = weight_poll
        self._weight_version_fn = weight_version
        self._copy_updates = copy_updates
        self.weight_version = int(weight_version()) if weight_version else 0
        self.stats = stats if stats is not None else ServingStats()
        self.telemetry = telemetry if telemetry is not None else NULL_TELEMETRY
        self._client_timed = client_timed
        self.endpoint = endpoint
        # -- serving fleet (ISSUE 17) --
        self.server_id = server_id
        self._shard_map = shard_map
        self.queue_depth_bound = (sv.queue_depth_bound
                                  if queue_depth_bound is None
                                  else queue_depth_bound)
        self.local_stats = local_stats
        # grow/shrink moves whole shard groups between live servers:
        # the fleet holds this lock while detaching/importing, and the
        # dispatch path holds it across every cache mutation
        self.cache_lock = threading.Lock()
        if self.queue_depth_bound > 0 or sv.servers > 1:
            self.stats.admission_enabled = True
            if local_stats is not None:
                local_stats.admission_enabled = True
        # The serving forward runs on THIS process's default backend —
        # the accelerator, when there is one: central placement is the
        # point (SEED). On CPU hosts force f32 like the local policies
        # (bf16 is emulated and slower there). Fleet servers pin by
        # slot (device_index) so N loops spread over N devices.
        devs = jax.local_devices()
        self._device = devs[device_index % len(devs)]
        if self._device.platform != "tpu":
            net = _force_f32(net)
        self.net = net
        self.action_dim = net.action_dim
        # quantized serving (ISSUE 14): the SAME shared forward the
        # local policies build — the config knob flips all of them
        # together. The server's tick is its dispatch counter, so the
        # accuracy probe runs on a real live micro-batch every
        # quant_probe_interval dispatches.
        self._quant = net.config.inference_dtype != "f32"
        self.quant_stats = quant_stats
        self._quant_probe_interval = (cfg.telemetry.quant_probe_interval
                                      if self._quant else 0)
        if forward_fn is not None:
            # bench-only device stand-in (timed-forward emulation):
            # plain f32 signature, no quant probe, no warmup needed
            self._quant = False
            self._quant_probe_interval = 0
            self._fwd = forward_fn
        else:
            self._fwd = make_forward_fn(
                net, probe_interval=self._quant_probe_interval)
        if self._quant and not is_quant_bundle(params):
            # direct construction from raw params (cold start, the
            # standalone CLI): build the twin once here — the weight
            # poll hands over published bundles from then on
            params = jax.device_get(make_inference_bundle(net, params))
        self._params = _pin_params(params, self._device, copy=True)
        h, w, s = net.obs_hw
        self.cache = (cache if cache is not None
                      else StateCacheFromConfig(cfg, (h, w), s,
                                                net.state_half,
                                                net.action_dim))
        self.buckets = serve_buckets(self.max_batch)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._last_weight_poll = 0.0
        self._last_sweep = 0.0
        self.batches_dispatched = 0
        if forward_fn is None and (warmup if warmup is not None
                                   else sv.warmup):
            self._warmup((h, w, s))

    def _warmup(self, obs_hw: Tuple[int, int, int]) -> None:
        """AOT-compile every dispatch bucket at start — a lazy mid-run
        compile would park every connected client for its duration (the
        ingest stager learned this the hard way, PERF.md)."""
        h, w, s = obs_hw
        hd = self.net.state_half
        for b in self.buckets:
            args = (self._params,
                    np.zeros((b, h, w, s), np.float32),
                    np.zeros(b, np.int32),
                    np.zeros((b, 2, hd), np.float32))
            if self._quant:
                # tick 0 exercises the probe branch too (lax.cond
                # compiles both; this keeps warm-up honest about it)
                np.asarray(self._fwd(*args, np.int32(0), np.int32(b))[0])
            else:
                np.asarray(self._fwd(*args)[0])

    # -- lifecycle --

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def start(self) -> "PolicyServer":
        if self.running:
            return self
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="policy-server")
        self._thread.start()
        return self

    def stop(self, timeout: float = 5.0) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            self._thread = None

    # -- the loop --

    def _loop(self) -> None:
        try:
            while not self._stop.is_set():
                try:
                    first = self.endpoint.inbox.get(timeout=0.1)
                except queue.Empty:
                    self._idle_work()
                    continue
                batch = collect_batch(self.endpoint.inbox, first,
                                      self.max_batch, self.deadline_s,
                                      expected=self.cache.active_clients)
                self._shed_overflow()
                with self.cache_lock:
                    self._dispatch(batch)
                self._idle_work()
        except Exception:
            logging.getLogger(__name__).exception(
                "policy server loop died; clients will time out and back "
                "off until a replacement starts")

    def _each_stats(self):
        yield self.stats
        if self.local_stats is not None:
            yield self.local_stats

    def _shed_overflow(self) -> None:
        """Admission control (ISSUE 17): after each batch fill, shed the
        OLDEST still-queued requests while the backlog exceeds
        ``queue_depth_bound`` — a fast STATUS_RETRY (with a retry-after
        hint one deadline out) instead of letting batch_wait run away.
        Shedding the queue head converts the worst-latency waits into
        rejects the client backs off on (WorkerHealth ladder).
        Disconnects are never shed: retention bookkeeping must run."""
        bound = self.queue_depth_bound
        if bound <= 0:
            return
        inbox = self.endpoint.inbox
        shed = 0
        while inbox.qsize() > bound:
            try:
                req, cb = inbox.get_nowait()
            except queue.Empty:
                break
            if req.kind == KIND_DISCONNECT:
                now = time.monotonic()
                with self.cache_lock:
                    try:
                        if self.cache.release(req.client_id, now):
                            for st in self._each_stats():
                                st.on_clients(disconnects=1)
                    except MisroutedClient:
                        pass        # unowned client: disconnect is a no-op
                self._safe_reply(cb, Reply(
                    req.req_id, STATUS_OK,
                    weight_version=self.weight_version))
                continue
            shed += 1
            self._safe_reply(cb, Reply(
                req.req_id, STATUS_RETRY,
                retry_after_ms=self.cfg.serve.deadline_ms))
        if shed:
            for st in self._each_stats():
                st.on_shed(shed)

    def _misroute_reply(self, cb: Callable, req: Request) -> None:
        """Stale routing map: bounce with the CURRENT map so the routing
        client re-aims without a discovery round trip."""
        wire = (self._shard_map.to_wire()
                if self._shard_map is not None else None)
        for st in self._each_stats():
            st.on_misrouted(1)
        self._safe_reply(cb, Reply(req.req_id, STATUS_MISROUTED,
                                   shard_map=wire))

    def _idle_work(self) -> None:
        now = time.monotonic()
        sv = self.cfg.serve
        if (self._weight_poll is not None
                and now - self._last_weight_poll >= sv.weight_poll_interval_s):
            self._last_weight_poll = now
            fresh = self._weight_poll()
            if fresh is not None:
                from r2d2_tpu.actor.policy import _pin_params
                from r2d2_tpu.models.network import is_quant_bundle
                if self._quant and self.quant_stats is not None \
                        and is_quant_bundle(fresh):
                    # publish-time-twin staleness stamp: the publication
                    # this twin was quantized at, surfaced in the quant
                    # block alongside the agreement gauge
                    self.quant_stats.on_stamp(
                        int(np.asarray(fresh["stamp"])))
                self._params = _pin_params(fresh, self._device,
                                           copy=self._copy_updates)
                if self._weight_version_fn is not None:
                    self.weight_version = int(self._weight_version_fn())
        if now - self._last_sweep >= 1.0:
            self._last_sweep = now
            with self.cache_lock:
                evicted = self.cache.sweep(now)
                active = self.cache.active_clients
            for st in self._each_stats():
                if evicted:
                    st.on_clients(evictions=evicted)
                st.active_clients = active

    def _dispatch(self, batch: list) -> None:
        now = time.monotonic()
        tele = self.telemetry
        tele.observe("serve/batch_wait", max(now - batch[0][0].t_recv, 0.0))
        for req, _cb in batch:
            tele.observe("serve/enqueue", max(now - req.t_recv, 0.0))
        for st in self._each_stats():
            st.on_requests(len(batch))
        live: List[Tuple[Request, Callable, int]] = []
        ev0 = self.cache.evictions
        co0, rc0 = self.cache.connects, self.cache.reconnects
        for req, cb in batch:
            if req.kind == KIND_DISCONNECT:
                try:
                    released = self.cache.release(req.client_id, now)
                except MisroutedClient:
                    self._misroute_reply(cb, req)
                    continue
                if released:
                    for st in self._each_stats():
                        st.on_clients(disconnects=1)
                self._safe_reply(cb, Reply(req.req_id, STATUS_OK,
                                           weight_version=self.weight_version))
                continue
            if self.ttl_s > 0 and now - req.t_recv > self.ttl_s:
                # stale backlog (e.g. queued against a dead server):
                # drop WITHOUT touching state — the client has long
                # since timed out and will resend current state. Aged on
                # the SERVER-side arrival stamp (t_recv), which is
                # comparable across processes and hosts; the client's
                # t_submit monotonic clock is neither.
                for st in self._each_stats():
                    st.on_expired()
                self._safe_reply(cb, Reply(req.req_id, STATUS_EXPIRED))
                continue
            try:
                slot, fresh = self.cache.lease(req.client_id, now)
            except MisroutedClient:
                self._misroute_reply(cb, req)
                continue
            if fresh:
                # unknown client (first contact, post-eviction, or a
                # server that restarted and lost the cache): start from
                # the episode-reset state — the local policy's
                # reset_state semantics
                self.cache.reset_slot(slot)
                self.cache.reset_op(slot)
            elif req.op_seq >= 0:
                last = int(self.cache.op_seq[slot])
                if req.op_seq == last:
                    # duplicate of an ALREADY-APPLIED op (the client
                    # timed out and retried, but the first copy was
                    # processed and its reply lost): replay the cached
                    # result — state advanced exactly once per logical
                    # step, no matter how many copies arrive
                    action, q = self.cache.cached_reply(slot)
                    self._safe_reply(cb, Reply(
                        req.req_id, STATUS_OK, action, q,
                        self.cache.hidden[slot].copy(),
                        weight_version=self.weight_version))
                    for st in self._each_stats():
                        st.on_replies(1)
                    continue
                if req.op_seq < last:
                    # older than the applied horizon: a stale copy the
                    # client has already moved past — never re-apply
                    for st in self._each_stats():
                        st.on_expired()
                    self._safe_reply(cb, Reply(req.req_id, STATUS_EXPIRED))
                    continue
            if req.reset_obs is not None:
                self.cache.reset_slot(slot, req.reset_obs)
            elif req.obs is not None:
                self.cache.observe(slot, req.obs, req.action)
            live.append((req, cb, slot))
        for st in self._each_stats():
            st.on_clients(
                connects=self.cache.connects - co0,
                reconnects=self.cache.reconnects - rc0,
                evictions=self.cache.evictions - ev0)
            st.active_clients = self.cache.active_clients
        if not live:
            return
        # distributed tracing (ISSUE 19): close each traced request's
        # route/transit hops and record its micro-batch fill wait (the
        # server's own monotonic clock — exact); the batch's forward and
        # reply hops follow below iff any request was traced
        traced_any = False
        trace_sinks = [st.trace for st in self._each_stats()
                       if st.trace is not None]
        if trace_sinks:
            for req, _cb, _slot in live:
                tr = getattr(req, "trace", None)
                if tr is not None:
                    traced_any = True
                    qw = max(now - req.t_recv, 0.0)
                    for sink in trace_sinks:
                        sink.on_request(tr, qw)
        fill = len(live)
        stacked, last_action, hidden = self.cache.gather(
            [slot for _, _, slot in live])
        bucket = next(b for b in self.buckets if b >= fill)
        if bucket > fill:
            pad = bucket - fill
            stacked = np.concatenate(
                [stacked, np.zeros((pad,) + stacked.shape[1:],
                                   stacked.dtype)])
            last_action = np.concatenate(
                [last_action, np.full(pad, -1, last_action.dtype)])
            hidden = np.concatenate(
                [hidden, np.zeros((pad,) + hidden.shape[1:], hidden.dtype)])
        t0 = time.perf_counter()
        if self._quant:
            from r2d2_tpu.actor.policy import feed_quant_probe
            # live=fill: the probe masks the bucket's padding rows out
            # of the agreement/|dQ| signal
            actions, q, h, probe = self._fwd(
                self._params, stacked, last_action, hidden,
                np.int32(self.batches_dispatched), np.int32(fill))
            feed_quant_probe(self.quant_stats, self._quant_probe_interval,
                             probe, lanes=fill,
                             tick=self.batches_dispatched)
        else:
            actions, q, h = self._fwd(self._params, stacked, last_action,
                                      hidden)
        actions = np.asarray(actions)
        q = np.asarray(q)
        h = np.asarray(h)
        t1 = time.perf_counter()
        tele.observe("serve/forward", t1 - t0)
        if tele.spans.enabled:
            # the serving plane's track in the cross-process Perfetto
            # merge (ISSUE 19): one span per dispatched micro-batch
            wall = time.time()
            tele.record_span("serve/forward", wall - (t1 - t0), wall,
                             {"fill": fill})
        reply_t = time.monotonic()
        for i, (req, cb, slot) in enumerate(live):
            if req.kind == KIND_STEP:
                self.cache.write_hidden(slot, h[i])
            if req.op_seq >= 0:
                self.cache.record_op(slot, req.op_seq, int(actions[i]),
                                     q[i])
            self._safe_reply(cb, Reply(
                req.req_id, STATUS_OK, int(actions[i]), q[i].copy(),
                h[i].copy(), weight_version=self.weight_version))
            lat = max(reply_t - req.t_recv, 0.0)
            for st in self._each_stats():
                if not self._client_timed:
                    st.on_request_latency(lat)
                if st.admission_enabled:
                    # the brownout contract's p99: server-side
                    # receive→reply of ADMITTED requests only
                    st.on_admitted_latency(lat)
        reply_s = time.perf_counter() - t1
        tele.observe("serve/reply", reply_s)
        if tele.spans.enabled:
            wall = time.time()
            tele.record_span("serve/reply", wall - reply_s, wall)
        if traced_any:
            for sink in trace_sinks:
                sink.on_batch(t1 - t0, reply_s)
        for st in self._each_stats():
            st.on_replies(fill)
            st.on_batch(
                fill,
                hit_full=len(batch) >= self.max_batch,
                hit_deadline=(len(batch) < self.max_batch
                              and now - batch[0][0].t_recv >= self.deadline_s),
                starved=(fill == 1 and self.cache.active_clients > 1))
        self.batches_dispatched += 1

    @staticmethod
    def _safe_reply(cb: Callable, reply: Reply) -> None:
        try:
            cb(reply)
        except Exception:
            pass                    # a dead client must not kill the server


def StateCacheFromConfig(cfg, frame_hw, frame_stack, hidden_dim,
                         action_dim: int = 1):
    from r2d2_tpu.serve.state_cache import StateCache
    sv = cfg.serve
    return StateCache(sv.state_slots, sv.state_shards, frame_hw,
                      frame_stack, hidden_dim,
                      lease_timeout_s=sv.lease_timeout_s,
                      action_dim=action_dim)
