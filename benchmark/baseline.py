"""Reference WAN baseline points for plot overlays.

The reference's published numbers (BASELINE.md; best run per results
file under reference benchmark/data/2-chain/results/) were measured on
10-50 m5d.8xlarge instances across five AWS regions — hardware this
framework's dev rig (one CPU core, one TPU chip) cannot match
in absolute throughput.  The overlay exists so the WAN-emulated runs
(--wan: the same 5-region delay topology on localhost) can be compared
against the reference's latency/fault-degradation SHAPE honestly,
with the hardware gap visible rather than hidden.
"""

# (label, consensus_tps, consensus_latency_ms) — 2-chain WAN, 0 faults
REFERENCE_WAN_POINTS = [
    ("ref 10 nodes (WAN, 10 hosts)", 99_512, 1_286),
    ("ref 20 nodes (WAN, 20 hosts)", 114_018, 2_328),
    ("ref 50 nodes (WAN, 50 hosts)", 97_861, 1_223),
]

# (faults, tps_range, latency_ms_range) at 10 nodes
REFERENCE_WAN_FAULTS = [
    (1, (63_000, 87_000), (2_600, 3_100)),
    (3, (8_500, 16_000), (5_400, 26_700)),
]
