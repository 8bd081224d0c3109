"""dnet-tpu: TPU-native distributed LLM inference.

A from-scratch TPU-first framework with the capabilities of dnet
(distributed pipelined-ring LLM inference): an OpenAI-compatible API node
drives a ring of shard nodes, each computing a contiguous window of
transformer layers on TPU via jit-compiled JAX, with activations hopping
between shards over ICI (`lax.ppermute` inside one XLA program) when they
share a slice, or over gRPC/DCN when they do not.  Layer weights stream
between host DRAM and TPU HBM so models larger than total HBM can run.
"""

__version__ = "0.4.0"
