"""WAN link-delay emulation for local benchmarks.

Every reference baseline number is a 5-region AWS WAN run
(reference benchmark/settings.json:18-26), while local runs see sub-ms
RTTs — an apples-to-oranges comparison (VERDICT r3 item 3).  This module
injects per-link propagation delay + jitter at the SENDER layer so a
localhost committee experiences the reference's topology:

- a spec file (``HOTSTUFF_WAN_SPEC``, read in ``Consensus.spawn`` where
  the committee and the node's name are known) places every node in a
  region and carries a symmetric ONE-WAY delay matrix between regions
  (defaults model the reference's us-east-1 / eu-north-1 /
  ap-southeast-2 / us-west-1 / ap-northeast-1 spread).  ``regions`` has
  two forms:

  - a **map** from committee address (``"host:port"``) to region, which
    whoever chose the ports writes (``build_spec``; ``benchmark local
    --wan``);
  - a **list** of region names, which places nodes by their position
    in the committee: node ``i`` of ``Committee.sorted_keys()``, the
    leader rotation's order (``consensus/leader.py``), lies in
    ``regions[i mod len(regions)]``, and a peer's region is found the
    same way from its address through the committee.  The leader of
    round ``r`` is key ``r mod n``, so the rotation walks the regions
    in one fixed cycle whatever keys a seed draws and whatever ports a
    run is given: a configuration's own file can be the spec
    (``chipbench/configs/wan50.json``).

  Keys of the file that the model does not know are ignored;
- senders delay each outbound message independently (deliver-at
  scheduling, FIFO-clamped per link — pipelined like real propagation,
  never head-of-line rate-limited);
- the reliable sender also delays ACK future *resolution* by the return
  leg, so the proposer's 2f+1-ACK back-pressure sees full RTTs.

The emulation lives in the asyncio senders: under ``--transport
native`` (the C++ reactor does its own I/O) a spec is refused at boot
(``WanSpecError``), since a silent skip would pass loopback numbers off
as WAN numbers.  ``WAN_COUNTS`` counts the frames a ``LinkScheduler``
held and the time it held them for (``wan_frames=`` / ``wan_delay_ms=``
of the ``Host stats:`` line, ``telemetry/hoststats.py``) and what their
links' matrix entries come to (``wan_base_ms=``): held over base is 1
but for the jitter's mean and the FIFO clamp.

Modeling notes (honest limitations): bandwidth is not modeled (consensus
messages are KB-scale — latency-bound, not bandwidth-bound, SURVEY §2.7);
receiver-side ACK writes to SimpleSender peers are not delayed (those
ACKs are sunk unread); the benchmark client is co-located with its nodes
(the reference runs one client per instance, local.py:79-91), so
client->node links stay fast.
"""

from __future__ import annotations

import asyncio
import json

from ..utils.clock import default_clock, default_rng

Address = tuple[str, int]

# Default one-way delays (ms) between the reference's five regions,
# derived from typical inter-region RTTs (RTT/2).  Intra-region ~0.5 ms.
DEFAULT_REGIONS = (
    "us-east-1",
    "eu-north-1",
    "ap-southeast-2",
    "us-west-1",
    "ap-northeast-1",
)
DEFAULT_MATRIX = {
    ("us-east-1", "eu-north-1"): 55.0,
    ("us-east-1", "ap-southeast-2"): 100.0,
    ("us-east-1", "us-west-1"): 30.0,
    ("us-east-1", "ap-northeast-1"): 75.0,
    ("eu-north-1", "ap-southeast-2"): 140.0,
    ("eu-north-1", "us-west-1"): 80.0,
    ("eu-north-1", "ap-northeast-1"): 120.0,
    ("ap-southeast-2", "us-west-1"): 70.0,
    ("ap-southeast-2", "ap-northeast-1"): 55.0,
    ("us-west-1", "ap-northeast-1"): 50.0,
}
INTRA_REGION_MS = 0.5
DEFAULT_JITTER_PCT = 10.0


def _addr_key(address: Address) -> str:
    return f"{address[0]}:{address[1]}"


def build_spec(addresses: list[Address]) -> dict:
    """A spec assigning committee addresses round-robin over the five
    default regions (the reference runs one node per instance spread
    over its regions the same way)."""
    regions = {
        _addr_key(a): DEFAULT_REGIONS[i % len(DEFAULT_REGIONS)]
        for i, a in enumerate(addresses)
    }
    matrix = {
        f"{a}|{b}": ms for (a, b), ms in DEFAULT_MATRIX.items()
    }
    return {
        "regions": regions,
        "matrix_one_way_ms": matrix,
        "intra_region_ms": INTRA_REGION_MS,
        "jitter_pct": DEFAULT_JITTER_PCT,
    }


class WanSpecError(ValueError):
    """A spec this node cannot serve: refused at boot."""


class WanCounts:
    """Frames a ``LinkScheduler`` held, the seconds it held them for
    and the seconds their links' matrix entries come to, jitter apart,
    both senders, every node of the process since it started: the
    ``wan_frames=``, ``wan_delay_ms=`` and ``wan_base_ms=`` of the
    ``Host stats:`` line (``telemetry/hoststats.py``).  An ACK's return
    leg is no frame."""

    def __init__(self):
        self.frames = 0
        self.delay_s = 0.0
        self.base_s = 0.0


#: the process's one count, as ``store/engine.py`` ``WAL_COUNTS`` is
WAN_COUNTS = WanCounts()


def place_by_rotation(regions: list[str], committee) -> dict[str, str]:
    """The list form of ``regions`` resolved to the map form: node ``i``
    of the committee's sorted key order, the leader rotation's, lies in
    ``regions[i mod len(regions)]``."""
    return {
        _addr_key(committee.address(name)): regions[i % len(regions)]
        for i, name in enumerate(committee.sorted_keys())
    }


def mean_link_ms(spec: dict, nodes: int) -> float:
    """What a list spec's delays come to over every directed node->node
    link of an ``nodes``-strong committee, each link weighing the same:
    the expectation a run's ``wan_delay_ms`` / ``wan_frames`` is held
    to (leaders rotate and every node votes, so a committee's frames
    spread evenly over its links)."""
    regions = spec["regions"]
    model = WanModel({**spec, "regions": {}}, ("", 0))
    total = sum(
        model.base_ms(regions[i % len(regions)], regions[j % len(regions)])
        for i in range(nodes)
        for j in range(nodes)
        if i != j
    )
    return total / (nodes * (nodes - 1))


class WanModel:
    """Per-link one-way delay sampling from a spec."""

    def __init__(self, spec: dict, self_address: Address, committee=None):
        regions = spec["regions"]
        if isinstance(regions, list):
            if committee is None or not regions:
                raise WanSpecError(
                    "a WAN spec whose regions are a list places nodes by "
                    "their position in the committee: it needs one, and "
                    "at least one region"
                )
            regions = place_by_rotation(regions, committee)
        self.regions: dict[str, str] = regions
        self.matrix: dict[tuple[str, str], float] = {}
        for key, ms in spec["matrix_one_way_ms"].items():
            a, b = key.split("|")
            self.matrix[(a, b)] = float(ms)
            self.matrix[(b, a)] = float(ms)
        self.intra_ms = float(spec.get("intra_region_ms", INTRA_REGION_MS))
        self.jitter_pct = float(spec.get("jitter_pct", DEFAULT_JITTER_PCT))
        self.self_region = self.regions.get(_addr_key(self_address))
        #: this node's place among the spec's nodes (the rotation's
        #: order under the list form, the file's under the map form)
        self.position = (
            list(self.regions).index(_addr_key(self_address))
            if self.self_region is not None
            else None
        )

    @classmethod
    def load(
        cls, path: str, self_address: Address, committee=None
    ) -> "WanModel":
        with open(path) as f:
            return cls(json.load(f), self_address, committee)

    def base_ms(self, src_region: str, dst_region: str) -> float:
        """The matrix's one-way delay between two regions, jitter apart."""
        if src_region == dst_region:
            return self.intra_ms
        return self.matrix.get((src_region, dst_region), self.intra_ms)

    def link(self, dst: Address) -> "Link":
        """The sampler of the link from this node to ``dst``, what a
        sender's ``link_delay`` hook returns.  Unknown peers (not in
        the spec — e.g. a client) get zero."""
        dst_region = self.regions.get(_addr_key(dst))
        if self.self_region is None or dst_region is None:
            return Link(0.0, 0.0)
        return Link(
            self.base_ms(self.self_region, dst_region), self.jitter_pct
        )

    def delay(self, dst: Address) -> float:
        """Sampled one-way delay (seconds) from this node to ``dst``."""
        return self.link(dst)()


class Link:
    """One directed link of a model: called, a sampled one-way delay in
    seconds; ``base_s`` is its matrix entry, jitter apart."""

    __slots__ = ("base_s", "_sigma_s")

    def __init__(self, base_ms: float, jitter_pct: float):
        self.base_s = base_ms / 1e3
        self._sigma_s = self.base_s * jitter_pct / 100.0

    def __call__(self) -> float:
        if not self.base_s:
            return 0.0
        return max(0.0, self.base_s + default_rng().gauss(0.0, self._sigma_s))


class LinkScheduler:
    """Deliver-at scheduling for one link: each message is delayed
    independently (pipelined), with FIFO clamping so jitter can never
    reorder frames on the TCP stream."""

    __slots__ = ("_delay_fn", "_base_s", "_last_at")

    def __init__(self, delay_fn):
        self._delay_fn = delay_fn
        # a model's ``Link`` knows its matrix entry; a plain callable
        # (a test's, the sim plane's fixed hop) is its own expectation
        self._base_s = getattr(delay_fn, "base_s", None)
        self._last_at = 0.0

    def deliver_at(self) -> float:
        now = asyncio.get_running_loop().time()
        drawn = self._delay_fn()
        at = now + drawn
        self._last_at = at = max(at, self._last_at)
        WAN_COUNTS.frames += 1
        WAN_COUNTS.delay_s += at - now
        WAN_COUNTS.base_s += drawn if self._base_s is None else self._base_s
        return at

    @staticmethod
    async def wait_until(at: float) -> None:
        remaining = at - asyncio.get_running_loop().time()
        if remaining > 0:
            await default_clock().sleep(remaining)


__all__ = [
    "DEFAULT_REGIONS",
    "Link",
    "LinkScheduler",
    "WAN_COUNTS",
    "WanModel",
    "WanSpecError",
    "build_spec",
    "mean_link_ms",
    "place_by_rotation",
]
