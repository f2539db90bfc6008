"""The one general traffic generator and the one load client.

A traffic mix is a data file (``traffic/<name>.json``); this module turns it
and a seed into requests and sends them over HTTP/SSE from ONE thread
(``selectors``), stamping every token as it is received.  No jax here.

Every seed gets the SAME set of sizes and inter-arrival gaps (the quantiles of
the mix's distributions), in another order, and its own token ids: the seed
must not change the amount of work.
"""
import json
import math
import random
import re
import selectors
import socket
import statistics
import time

_NORMAL = statistics.NormalDist()


def _quantile(dist, u):
    """The u-quantile (0 < u < 1) of a length distribution of the mix."""
    kind = dist["dist"]
    if kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(u))
    else:
        raise ValueError(f"no such length distribution: {kind!r}")
    return int(min(max(round(x), dist.get("min", 1)), dist.get("max", x)))


def sizes(traffic, n):
    """n ``(prompt_tokens, output_tokens)`` pairs: the quantiles of the two
    distributions, paired by a fixed shuffle so the two are independent, the
    sum capped at ``total_max``.  The same for every seed."""
    us = [(i + 0.5) / n for i in range(n)]
    prompts = [_quantile(traffic["prompt_tokens"], u) for u in us]
    outputs = [_quantile(traffic["output_tokens"], u) for u in us]
    random.Random(0).shuffle(outputs)
    cap = traffic.get("total_max")
    if cap:
        outputs = [min(o, cap - p) for p, o in zip(prompts, outputs)]
    return list(zip(prompts, outputs))


def arrival_gaps(traffic, n, span):
    """n inter-arrival gaps that sum to ``span`` seconds: the quantiles of the
    arrival process at the mix's rate.  The same for every seed."""
    arr = traffic["arrivals"]
    us = [(i + 0.5) / n for i in range(n)]
    if arr["process"] == "poisson":
        gaps = [-math.log(1.0 - u) for u in us]
    else:
        raise ValueError(f"no such arrival process: {arr['process']!r}")
    scale = span / sum(gaps)
    return [g * scale for g in gaps]


def _shuffle(rng, items, block):
    """Shuffle in place: wholly, or within consecutive blocks of ``block``
    items, which keeps every stretch of the run the same work for every seed
    while the order inside it changes."""
    if not block:
        rng.shuffle(items)
        return
    for i in range(0, len(items), block):
        part = items[i:i + block]
        rng.shuffle(part)
        items[i:i + block] = part


def _tokens(rng, n, vocab, prefix):
    body = [rng.randrange(vocab) for _ in range(n - len(prefix))]
    return (prefix + body)[:n]


def build_requests(traffic, seed, vocab, seconds, rate=None):
    """The run's requests from the mix and the seed.

    Open loop: ``[{"due": s, "tokens": [...], "max_new_tokens": n}]`` sorted
    by due time, covering ramp + window exactly.  Closed loop: one such list
    per client (no due times), long enough to outlast the run."""
    rng = random.Random(int(seed))
    prefix = [rng.randrange(vocab)
              for _ in range(int(traffic.get("shared_prefix_tokens", 0)))]
    span = float(traffic.get("ramp_seconds", 0)) + float(seconds)

    def request(size):
        return {"tokens": _tokens(rng, size[0], vocab, prefix),
                "max_new_tokens": size[1]}

    if traffic["loop"] == "open":
        rate = float(rate if rate is not None
                     else traffic["arrivals"]["rate_per_s"])
        n = max(1, round(rate * span))
        block = int(traffic.get("shuffle_block", 0))
        # one fixed shuffle spreads the quantiles over the run; the seed
        # then reorders them, wholly or block by block
        order = sizes(traffic, n)
        gaps = arrival_gaps(traffic, n, span)
        fixed = random.Random(1)
        fixed.shuffle(order)
        fixed.shuffle(gaps)
        _shuffle(rng, order, block)
        _shuffle(rng, gaps, block)
        due, out = 0.0, []
        for size, gap in zip(order, gaps):
            out.append({"due": due, **request(size)})
            due += gap
        return out
    if traffic["loop"] == "closed":
        clients = int(traffic["clients"])
        per_client = int(traffic.get("requests_per_client", 16))
        order = sizes(traffic, clients * per_client)
        rng.shuffle(order)
        return [[request(order[c * per_client + i])
                 for i in range(per_client)] for c in range(clients)]
    raise ValueError(f"no such loop for a served mix: {traffic['loop']!r}")


# ---------------------------------------------------------------------------
# the client
# ---------------------------------------------------------------------------
_EVENT = re.compile(rb"event: (\w+)\r?\ndata: (.*?)\r?\n\r?\n", re.S)
_TOKEN = re.compile(rb'"token": (-?\d+)')


class Stream:
    """One request on the wire."""

    __slots__ = ("req", "client", "due", "sent", "sock", "buf", "tokens",
                 "times", "status", "done", "failed", "error")

    def __init__(self, req, due, client=None):
        self.req, self.due, self.client = req, due, client
        self.sent = None
        self.sock = None
        self.buf = b""
        self.tokens, self.times = [], []
        self.status = None
        self.done = self.failed = False
        self.error = None


class LoadClient:
    """Sends requests to ``/v1/models/<model>:generate`` with ``stream: true``
    and reads the SSE frames, all from the calling thread.  Times are
    ``time.monotonic()`` seconds."""

    def __init__(self, port, model, host="127.0.0.1"):
        self.addr = (host, port)
        self.path = f"/v1/models/{model}:generate"
        self.sel = selectors.DefaultSelector()
        self.streams = []
        self.inflight = 0

    def launch(self, req, due, client=None):
        st = Stream(req, due, client)
        self.streams.append(st)
        body = json.dumps({"tokens": req["tokens"], "stream": True,
                           **{k: v for k, v in req.items()
                              if k not in ("tokens", "due")}}).encode()
        head = (f"POST {self.path} HTTP/1.1\r\nHost: bench\r\n"
                "Content-Type: application/json\r\nConnection: close\r\n"
                f"Content-Length: {len(body)}\r\n\r\n").encode()
        try:
            st.sock = socket.create_connection(self.addr, timeout=10)
            st.sent = time.monotonic()
            st.sock.sendall(head + body)
            st.sock.setblocking(False)
        except OSError as e:
            st.sent = st.sent or time.monotonic()
            self._fail(st, f"connect/send: {e}")
            return st
        self.sel.register(st.sock, selectors.EVENT_READ, st)
        self.inflight += 1
        return st

    def _fail(self, st, why):
        st.failed, st.error = True, why
        self._close(st)

    def _close(self, st):
        if st.sock is not None:
            try:
                self.sel.unregister(st.sock)
                self.inflight -= 1
            except (KeyError, ValueError):
                pass
            st.sock.close()
            st.sock = None

    def poll(self, timeout):
        """Wait up to ``timeout`` for bytes; returns the streams that ended
        (done or failed) in this call."""
        ended = []
        for key, _ in self.sel.select(max(0.0, timeout)):
            st = key.data
            now = time.monotonic()
            try:
                data = st.sock.recv(1 << 16)
            except BlockingIOError:
                continue
            except OSError as e:
                self._fail(st, f"recv: {e}")
                ended.append(st)
                continue
            if not data:
                if not st.done:
                    self._fail(st, "closed before the done event")
                    ended.append(st)
                else:
                    self._close(st)
                continue
            st.buf += data
            if self._parse(st, now):
                ended.append(st)
        return ended

    def _parse(self, st, now):
        """Consume whole frames from the buffer; True when the stream ended."""
        if st.status is None:
            end = st.buf.find(b"\r\n\r\n")
            if end < 0:
                return False
            st.status = int(st.buf[:end].split(b" ", 2)[1])
            st.buf = st.buf[end + 4:]
            if st.status != 200:
                self._fail(st, f"HTTP {st.status}: {st.buf[:200]!r}")
                return True
        pos = 0
        for m in _EVENT.finditer(st.buf):
            pos = m.end()
            kind = m.group(1)
            if kind == b"token":
                st.tokens.append(int(_TOKEN.search(m.group(2)).group(1)))
                st.times.append(now)
            elif kind == b"done":
                final = json.loads(m.group(2))["tokens"]
                if final != st.tokens:
                    self._fail(st, "the done frame disagrees with the "
                                   "streamed tokens")
                else:
                    st.done = True
                    self._close(st)
                return True
            else:
                self._fail(st, f"SSE {kind!r}: {m.group(2)[:200]!r}")
                return True
        st.buf = st.buf[pos:]
        return False

    def abandon(self):
        """Close what is still in flight (the server sees a disconnect and
        frees the slot)."""
        for st in self.streams:
            if st.sock is not None:
                self._close(st)

    def close(self):
        self.abandon()
        self.sel.close()


def run_open(client, requests, t0, on_tick=None):
    """Send each request when it is due (``t0 + due``), until the schedule
    is exhausted; keeps reading meanwhile."""
    i = 0
    while i < len(requests):
        now = time.monotonic()
        while i < len(requests) and t0 + requests[i]["due"] <= now:
            client.launch(requests[i], t0 + requests[i]["due"])
            i += 1
        if i >= len(requests):
            break
        wait = t0 + requests[i]["due"] - time.monotonic()
        client.poll(min(max(wait, 0.0), 0.05))
        if on_tick:
            on_tick(time.monotonic())


def run_closed(client, per_client, t_end, on_tick=None):
    """Every client sends its next request when its last completes, until
    ``t_end``."""
    nxt = [0] * len(per_client)

    def send(c):
        # a client that outruns its list starts it again (the mix's
        # ``requests_per_client`` should make that rare: a repeat can hit
        # the prefix cache)
        client.launch(per_client[c][nxt[c] % len(per_client[c])],
                      time.monotonic(), c)
        nxt[c] += 1

    for c in range(len(per_client)):
        send(c)
    while time.monotonic() < t_end:
        for st in client.poll(0.05):
            if time.monotonic() < t_end:
                send(st.client)
        if on_tick:
            on_tick(time.monotonic())
