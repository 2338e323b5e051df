"""The one traffic generator: a mix is a JSON file of parameters.

Sizes and arrivals are stratified: each is drawn at the fixed quantiles
``(i + 0.5) / n`` of its distribution and only their order comes from the
seed, so every seed offers the same work in another order. The token ids
of the prompts come from the seed.

An open loop (``"loop": "open"``) is a schedule of send times over the
window: Poisson arrivals at ``rate_per_s``, users drawn Zipf(``zipf_s``)
over ``users``. A closed loop (``"loop": "closed"``) is one list of
requests per client (``clients``, or ``"lanes"`` for one per lane); a
client sends its next request when the last one finished. Lengths are
stratified in blocks of ``strata`` requests per client, so every block of a
client's list holds the same sizes.

With ``"returning": true`` a user's requests continue one conversation
(one session in the engine) until its next turn would pass ``max_len``;
then the user starts a new one. Otherwise every request is a conversation
of its own. A conversation's key is ``<user>.c<n>``.

Each request is marked ``sampled`` with probability ``sample_share``: the
correctness check compares the sampled requests that finished in the
window, with their conversations up to them.
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Item:
    index: int
    user: str
    conv: str                 # session key in the engine
    start_pos: int            # position of the conversation at this turn
    prompt: list
    max_new: int
    send: float | None = None  # open loop: seconds after the window opens
    client: int | None = None  # closed loop
    sampled: bool = False
    last_turn: bool = False    # the conversation ends after this turn

    @property
    def positions(self) -> int:
        """Positions this turn occupies: the last generated token is never
        fed back."""
        return len(self.prompt) + self.max_new - 1


def _quantile(dist: dict, q: float) -> float:
    kind = dist["dist"]
    if kind == "lognormal":
        v = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(q))
    elif kind == "loguniform":
        v = dist["min"] * (dist["max"] / dist["min"]) ** q
    elif kind == "uniform":
        v = dist["min"] + q * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return min(max(v, dist["min"]), dist["max"])


def stratified(dist: dict, n: int) -> np.ndarray:
    """n lengths at the quantiles (i + 0.5) / n, in ascending order."""
    return np.array([int(round(_quantile(dist, (i + 0.5) / n)))
                     for i in range(n)])


def _zipf_users(users: int, s: float, n: int) -> np.ndarray:
    """n user ranks at the stratified quantiles of Zipf(s) over users."""
    p = 1.0 / np.arange(1, users + 1) ** s
    cdf = np.cumsum(p / p.sum())
    q = (np.arange(n) + 0.5) / n
    return np.minimum(np.searchsorted(cdf, q), users - 1)


class _Conversations:
    """Splits each user's turns into conversations that fit max_len."""

    def __init__(self, max_len: int, returning: bool):
        self.max_len, self.returning = max_len, returning
        self.pos, self.n, self.last = {}, {}, {}

    def place(self, item: Item) -> None:
        if item.positions > self.max_len:
            raise ValueError(f"a turn of {item.positions} positions cannot "
                             f"fit max_len={self.max_len}")
        u = item.user
        pos = self.pos.get(u)
        if not self.returning or pos is None or \
                pos + item.positions > self.max_len:
            if u in self.last:
                self.last[u].last_turn = True
            self.n[u] = self.n.get(u, -1) + 1
            pos = 0
        item.conv, item.start_pos = f"{u}.c{self.n[u]}", pos
        self.pos[u] = pos + item.positions
        self.last[u] = item
        if not self.returning:
            item.last_turn = True


def _prompt(rng, n: int, vocab: int) -> list:
    return rng.integers(1, vocab, n).tolist()


def open_loop(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """Items with send times in [0, seconds), in send order."""
    rng = np.random.default_rng([seed, 1])
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q) / mix["rate_per_s"])
    sends = np.cumsum(gaps)
    prompts = rng.permutation(stratified(mix["prompt"], n))
    outs = rng.permutation(stratified(mix["output"], n))
    users = rng.permutation(_zipf_users(mix["users"], mix["zipf_s"], n))
    sampled = rng.random(n) < mix["sample_share"]
    conv = _Conversations(mix["max_len"], mix["returning"])
    items = []
    for i in range(n):
        if sends[i] >= seconds:
            break
        it = Item(index=i, user=f"u{users[i]}", conv="", start_pos=0,
                  prompt=_prompt(rng, int(prompts[i]), vocab),
                  max_new=int(outs[i]), send=float(sends[i]),
                  sampled=bool(sampled[i]))
        conv.place(it)
        items.append(it)
    return items


def closed_loop(mix: dict, seed: int, vocab: int, clients: int) -> list:
    """One list of items per client, ``per_client`` long."""
    per, strata = mix["per_client"], mix["strata"]
    blocks = -(-per // strata)
    lists = []
    index = 0
    for c in range(clients):
        rng = np.random.default_rng([seed, 2, c])
        p = stratified(mix["prompt"], strata)
        o = stratified(mix["output"], strata)
        prompts = np.concatenate([rng.permutation(p) for _ in range(blocks)])
        outs = np.concatenate([rng.permutation(o) for _ in range(blocks)])
        sampled = rng.random(per) < mix["sample_share"]
        conv = _Conversations(mix["max_len"], mix["returning"])
        items = []
        for j in range(per):
            user = f"c{c}" if mix["returning"] else f"c{c}r{j}"
            it = Item(index=index, user=user, conv="", start_pos=0,
                      prompt=_prompt(rng, int(prompts[j]), vocab),
                      max_new=int(outs[j]), client=c,
                      sampled=bool(sampled[j]))
            conv.place(it)
            items.append(it)
            index += 1
        lists.append(items)
    return lists
