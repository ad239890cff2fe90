"""Seeded traffic and the closed-loop HTTP clients that send it.

One generator serves every mix; a mix is a data file under
`benchmarks/traffic/` (see `chat-closed.json`, `docs-repeat.json`):

    clients               closed-loop clients (threads of this process)
    stagger_s             delay between client starts
    sessions_per_client   sizes are drawn once per (client, session)
    shared_prefix_tokens  [lo, hi] or null: a document every ask of a
                          session starts with (one `session_id` each)
    asks_per_session      requests per session
    prompt_tokens         {"dist": "uniform"|"loguniform", "range": [lo, hi]}
                          fresh tokens of each request
    answer_tokens         same form: `max_new_tokens` of each request

Sizes are an evenly spaced grid over the distribution (not samples), so
every `--seed` does the same SET of work; the seed pairs the sizes, deals
them to the clients, orders them, and makes every token id (and the
weights). Callers do not coordinate: each sends its next request the
moment its last one ended, and the clock of a request starts when it is
sent. An open-loop schedule would be one more function here.
"""

from __future__ import annotations

import http.client
import json
import random
import statistics
import threading
import time


def _grid(spec: dict, n: int) -> list:
    lo, hi = spec["range"]
    qs = [(i + 0.5) / n for i in range(n)]
    if spec["dist"] == "loguniform":
        return [round(lo * (hi / lo) ** q) for q in qs]
    if spec["dist"] == "uniform":
        return [round(lo + (hi - lo) * q) for q in qs]
    raise ValueError(f"unknown dist {spec['dist']!r}")


def _grids(traffic: dict):
    """-> (prefixes, prompts, answers): the mix's sizes, in grid order."""
    n = traffic["clients"] * traffic["sessions_per_client"]
    asks = traffic.get("asks_per_session", 1)
    prefix = traffic.get("shared_prefix_tokens")
    prefixes = _grid({"dist": "uniform", "range": prefix}, n) if prefix \
        else [0] * n
    return (prefixes, _grid(traffic["prompt_tokens"], n * asks),
            _grid(traffic["answer_tokens"], n * asks))


def plan(traffic: dict, seed: int) -> list:
    """-> per client, its sessions in the order it will meet them (cycled
    when the list runs out). A session is {"prefix": n | 0, "asks":
    [(prompt_tokens, answer_tokens), ...]}. The set of sizes is the same
    for every seed; their pairing and order are the seed's."""
    clients = traffic["clients"]
    asks = traffic.get("asks_per_session", 1)
    shape = random.Random(seed * 7919 + 1)
    prefixes, prompts, answers = _grids(traffic)
    for sizes in (prefixes, prompts, answers):
        shape.shuffle(sizes)
    sessions = [{"prefix": prefix,
                 "asks": [(prompts[i * asks + j], answers[i * asks + j])
                          for j in range(asks)]}
                for i, prefix in enumerate(prefixes)]
    return [sessions[c::clients] for c in range(clients)]


def request_sizes(traffic: dict, block: int) -> set:
    """Every (tokens to prefill) the mix can ask of the engine under some
    seed: the whole prompt when nothing is cached, and the tail past the
    last whole cached block of the shared prefix on a repeat. Any prefix
    may meet any prompt, so the sizes are crossed."""
    prefixes, prompts, _ = _grids(traffic)
    out = set()
    for prefix in set(prefixes):
        for p in set(prompts):
            out.add(prefix + p)
            if prefix:
                out.add(prefix % block + p)
    return out


def tokens(rng: random.Random, n: int, vocab: int) -> list:
    return [rng.randrange(1, vocab) for _ in range(n)]


class Client(threading.Thread):
    """One closed-loop caller: sends its next request when the last ended."""

    def __init__(self, idx, sessions, seed, vocab, port, state):
        super().__init__(name=f"client-{idx}", daemon=True)
        self.idx, self.sessions, self.vocab = idx, sessions, vocab
        self.rng = random.Random(seed * 1009 + idx)
        self.seed, self.port, self.state = seed, port, state
        self.records = []
        self.error = None

    def run(self):
        try:
            time.sleep(self.idx * self.state["stagger_s"])
            conn = http.client.HTTPConnection(
                "127.0.0.1", self.port, timeout=self.state["timeout_s"])
            try:
                self._loop(conn)
            finally:
                conn.close()
        except Exception as e:  # noqa: BLE001 - reported by the harness
            self.error = e
            self.state["stop"].set()

    def _loop(self, conn):
        n = 0
        while True:
            s = self.sessions[n % len(self.sessions)]
            sid = f"s{self.seed}-c{self.idx}-n{n}" if s["prefix"] else None
            prefix = tokens(self.rng, s["prefix"], self.vocab)
            for p, a in s["asks"]:
                if self.state["stop"].is_set():
                    return
                body = {"prompt": prefix + tokens(self.rng, p, self.vocab),
                        "max_new_tokens": a}
                if sid:
                    body["session_id"] = sid
                self._one(conn, body, a)
            n += 1
            if n == 1:
                self.state["first_done"][self.idx].set()

    def _one(self, conn, body, want):
        """-> one record; times are time.perf_counter() seconds. `t_send`
        is taken before the request leaves, so every queue between the
        caller and the first token is inside the time to first token;
        `t_tokens` has the arrival of every token."""
        payload = json.dumps(body)
        rec = {"t_send": time.perf_counter(), "want": want, "t_tokens": [],
               "prompt": len(body["prompt"]), "ok": False}
        self.records.append(rec)
        try:
            self._stream(conn, payload, rec)
        except Exception as e:
            rec["error"] = repr(e)
            raise
        rec["t_end"] = time.perf_counter()
        if not rec["ok"]:
            rec.setdefault(
                "error", f"stream gave {len(rec['t_tokens'])} of {want} tokens")

    def _stream(self, conn, payload, rec):
        conn.request("POST", "/", body=payload,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            rec["error"] = f"HTTP {resp.status}: {resp.read()[:200]!r}"
            return
        done = False
        while True:
            line = resp.readline()
            if not line:
                break
            if not line.startswith(b"data: "):
                continue
            now = time.perf_counter()
            data = line[6:].strip()
            if data == b"[DONE]":
                done = True
                resp.read()
                break
            if b'"token"' in data:
                rec["t_tokens"].append(now)
        rec["ok"] = done and len(rec["t_tokens"]) == rec["want"]


def run_closed(traffic: dict, seed: int, seconds: float, port: int,
               vocab: int, on_window=None) -> dict:
    """Start the clients, open the window once every client has finished
    one whole session, measure for `seconds`, keep the load up until every
    request sent in the window has ended (they count, and end under the
    load they began under), stop, and reduce. `on_window("open" | "close")`
    is called at the window's two ends."""
    state = {"stagger_s": traffic.get("stagger_s", 0.0), "timeout_s": 600.0,
             "stop": threading.Event(),
             "first_done": [threading.Event()
                            for _ in range(traffic["clients"])]}
    clients = [Client(i, s, seed, vocab, port, state)
               for i, s in enumerate(plan(traffic, seed))]
    for c in clients:
        c.start()
    for ev in state["first_done"]:
        while not ev.wait(0.2):
            if state["stop"].is_set():
                break
    t_open = time.perf_counter()
    if on_window:
        on_window("open")
    state["stop"].wait(seconds)
    t_close = time.perf_counter()
    if on_window:
        on_window("close")

    def window_ended():
        return all("t_end" in r or "error" in r for c in clients
                   for r in list(c.records) if r["t_send"] < t_close)

    while not window_ended() and not state["stop"].wait(0.05):
        pass
    state["stop"].set()
    for c in clients:
        c.join(timeout=state["timeout_s"])
    errors = [repr(c.error) for c in clients if c.error is not None]
    errors += [f"{c.name} did not stop" for c in clients if c.is_alive()]
    records = [r for c in clients for r in c.records]
    return reduce_records(records, t_open, t_close, errors)


def quantile(values: list, q: float) -> float:
    """Nearest-rank-with-interpolation quantile (numpy's default)."""
    v = sorted(values)
    pos = q * (len(v) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def reduce_records(records, t_open, t_close, errors=()) -> dict:
    """A request belongs to the window when it was SENT in it: the ones in
    flight at the close are left to finish and count like the rest, so no
    long request is censored out of a quantile. Throughput is every token
    that arrived inside the window, whichever request it belongs to, over
    the window."""
    mine = [r for r in records if t_open <= r["t_send"] < t_close]
    ok = [r for r in mine if r["ok"]]
    window = t_close - t_open
    out_tokens = sum(t_open <= t < t_close
                     for r in records for t in r["t_tokens"])
    return {
        "attempted": len(mine),
        "failed": len(mine) - len(ok),
        "errors": list(errors) + [r["error"] for r in mine if "error" in r][:5],
        "window_s": window,
        "ttft_ms": [(r["t_tokens"][0] - r["t_send"]) * 1e3 for r in ok],
        "tpot_ms": [(r["t_tokens"][-1] - r["t_tokens"][0])
                    / (len(r["t_tokens"]) - 1) * 1e3
                    for r in ok if len(r["t_tokens"]) > 1],
        "out_tokens_per_s": out_tokens / window,
        "prompt_tokens": sum(r["prompt"] for r in ok),
        "requests_per_s": len(ok) / window,
    }


def interval_stats(stamps: list, t0: float, tokens_per_step: int,
                   chips: int) -> dict:
    """Train cell arithmetic: `stamps` are the host times at which each
    step's loss reached the host, `t0` the window's opening. The rate is
    every token of the window over all of its time (it ends with the last
    step), so a stall of the host lowers it. The median interval stands
    beside it for the step alone: `stall_share` is the part of the window
    that steps of the median length do not cover."""
    times = [t0] + list(stamps)
    gaps = [b - a for a, b in zip(times, times[1:])]
    med = statistics.median(gaps)
    window = times[-1] - t0
    return {
        "steps": len(gaps),
        "window_s": window,
        "step_ms": [g * 1e3 for g in gaps],
        "tokens_per_s_per_chip": len(gaps) * tokens_per_step / window / chips,
        "tokens_per_s_per_chip_of_median_step": tokens_per_step / med / chips,
        "stall_share": 100.0 * max(0.0, window - len(gaps) * med) / window,
    }
