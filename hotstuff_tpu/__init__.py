"""hotstuff_tpu: a TPU-native 2-chain HotStuff BFT consensus framework.

A ground-up rebuild of the capabilities of the reference Rust implementation
(tanZiWen/hotstuff, a fork of asonnino/hotstuff) designed TPU-first:

- the crypto hot path (Ed25519 vote-signature and quorum-certificate batch
  verification) runs as JAX kernels on TPU (``hotstuff_tpu.tpu``), behind a
  pluggable ``SignatureService`` boundary with a CPU default;
- the node runtime (consensus core, proposer, synchronizer, networking,
  store) is an asyncio actor graph mirroring the reference's tokio actor
  topology, with native C++ components under ``native/``;
- a benchmark harness (``benchmark/``) reproduces the reference's
  measurement methodology with a corrected log-schema contract.

Reference layer map: SURVEY.md §1; component parity: SURVEY.md §2.
"""

__version__ = "0.1.0"
