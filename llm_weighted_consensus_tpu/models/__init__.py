"""On-TPU models: BGE-class BERT text encoders + DeBERTa-style reward model.

The reference delegates all inference to upstream HTTP APIs and ships only
the embeddings *wire types* (SURVEY §2.9); here the encoder is a real
device model:

* ``bert``      — functional JAX BERT encoder (bge-small/base/large
  configs), bf16 matmuls with f32 layernorm/softmax, CLS/mean pooling;
* ``deberta``   — disentangled-attention encoder + scalar reward head
  (reward-model re-ranking);
* ``tokenizer`` — host-side WordPiece (real vocab when available, a
  deterministic hash tokenizer fallback so the pipeline always runs);
* ``embedder``  — tokenize -> jitted forward -> pooled embedding, exposing
  the OpenAI embeddings wire contract (types/embeddings.py).

Params are plain nested-dict pytrees: trivially shardable with
jax.sharding, checkpointable with orbax, no framework lock-in.
"""

from . import bert, configs, deberta, embedder, tokenizer  # noqa: F401
