"""One generator for every traffic file under `bench/traffic/`.

A traffic file is data:

- `loop`: "closed": `clients` callers, each sending its next request as soon
  as the last one completes (the only loop the benchmark drives so far).
- `sizes`: images per request, {"kind": "fixed", "value": n}.
- `classes`: the one deadline class of the requests, name ->
  {"share": 1.0, "budget_ms"}; the program's scheduler knows "interactive",
  "standard" and "relaxed" (serve/traffic.py).
- `payload_pool`: distinct uint8 images drawn from `--seed`; a request's
  images are a run of the pool from a seeded offset.
- `check_requests`: how many requests the output check compares.
- `serve`: the server the traffic is for: engine `buckets`, `replicas`
  (threads), and `calibrate_iters` for the scheduler's service model.

Every seed offers the same work: the same number of clients and the same
sizes. `--seed` draws the payloads, each request's offset in the pool and
the checked requests.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    client: int
    index: int           # the client's request count before this one
    size: int            # images
    klass: str
    budget_s: float
    img_offset: int      # first image in the payload pool
    check: bool          # in the client's check sample when it was sent


def request_size(sizes: dict) -> int:
    if sizes["kind"] != "fixed":
        raise ValueError(f"unknown size kind {sizes['kind']!r}")
    return int(sizes["value"])


class ClosedClients:
    """`clients` closed-loop callers. `next(c)` is client c's next request;
    each client draws from its own stream of the seed, so a request's
    contents do not depend on when the server completed the last one.

    The requests the output check compares are a sample, drawn from the
    seed, of all the requests a client sends: a reservoir of
    ceil(check_requests / clients) per client. A request enters it when it
    is sent (`Arrival.check`) and may leave it for a later one; `checked`
    says which are in it once the window has closed."""

    def __init__(self, traffic: dict, seed: int):
        if traffic["loop"] != "closed":
            raise ValueError(f"unknown loop {traffic['loop']!r}")
        self.n_clients = int(traffic["clients"])
        self.size = request_size(traffic["sizes"])
        self.pool = int(traffic["payload_pool"])
        (klass, spec), = traffic["classes"].items()
        self.klass, self.budget_s = klass, float(spec["budget_ms"]) / 1e3
        self._rngs = [np.random.default_rng([seed, c])
                      for c in range(self.n_clients)]
        self._check_rngs = [np.random.default_rng([seed, c, 2])
                            for c in range(self.n_clients)]
        self.per_client = -(-int(traffic["check_requests"]) // self.n_clients)
        self._reservoir = [[] for _ in range(self.n_clients)]
        self._sent = [0] * self.n_clients

    def next(self, client: int) -> Arrival:
        i = self._sent[client]
        self._sent[client] += 1
        res, k = self._reservoir[client], self.per_client
        j = int(self._check_rngs[client].integers(0, i + 1))
        check = i < k or j < k
        if i < k:
            res.append(i)
        elif j < k:
            res[j] = i
        return Arrival(client, i, self.size, self.klass, self.budget_s,
                       int(self._rngs[client].integers(0, self.pool)), check)

    def checked(self, client: int, index: int) -> bool:
        return index in self._reservoir[client]


def payload_pool(traffic: dict, image_shape, seed: int) -> np.ndarray:
    """(pool, H, W, C) uint8 images, what a client holds after decoding."""
    rng = np.random.default_rng([seed, 1])
    return rng.integers(0, 256, size=(int(traffic["payload_pool"]),)
                        + tuple(image_shape), dtype=np.uint8)


def payload_indices(img_offset: int, start: int, size: int, pool: int):
    """Pool rows of images [start, start + size) of a request."""
    return (img_offset + start + np.arange(size)) % pool
