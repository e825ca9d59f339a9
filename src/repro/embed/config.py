"""Light frozen config for the LM-embedding feature path.

``EmbedConfig`` is the ENGINE-side twin of ``repro.scenarios.EmbedSpec``:
a hashable frozen dataclass the stream router can carry inside
``StreamLearnerConfig`` (static jit argument) without importing the model
stack — nothing here touches jax, so ``repro.labelstream.router`` stays
importable on config-only paths. ``scenarios/compile.py`` lowers the
declarative spec to this config; ``repro.embed.bank`` consumes it.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

POOLING_KINDS = ("mean", "last")


@dataclasses.dataclass(frozen=True)
class EmbedConfig:
    """How task text becomes a feature vector.

    ``model`` names a ``repro.configs`` registry architecture;
    ``reduced=True`` runs it at smoke scale (d_model=64, vocab 256 — the
    in-loop bank-build setting). ``pooling`` collapses the (B, S, d_model)
    final-norm hidden states to one vector per task (masked mean or the
    last real token); a seeded Gaussian random projection then maps
    d_model down to ``FeatureSpec.n_features`` (``projection_dim`` is an
    optional redundant pin of that target width). ``bank_size`` is the
    number of precomputed task embeddings held device-resident by the
    :class:`~repro.embed.bank.EmbeddingBank`; ``batch_size`` is the
    encoder's largest micro-batch (a shorter chunk runs in its
    :func:`~repro.embed.encoder.bucket_rows`); ``seed`` fixes corpus
    tokens, model params and the projection, so the whole feature path is
    deterministic."""
    model: str = "xlstm-125m"
    reduced: bool = True
    pooling: str = "mean"         # "mean" | "last"
    seq_len: int = 48             # max tokens per task
    bank_size: int = 512          # precomputed embeddings (2*C*K layout)
    projection_dim: Optional[int] = None  # None = FeatureSpec.n_features
    batch_size: int = 64          # encoder's largest micro-batch
    seed: int = 0

    def __post_init__(self):
        def fail(field, msg):
            raise ValueError(f"EmbedConfig.{field}: {msg}")
        if self.pooling not in POOLING_KINDS:
            fail("pooling", f"must be one of {POOLING_KINDS}, "
                 f"got {self.pooling!r}")
        if self.seq_len < 4:
            fail("seq_len", f"must be >= 4, got {self.seq_len}")
        if self.bank_size < 2:
            fail("bank_size", f"must be >= 2, got {self.bank_size}")
        if self.projection_dim is not None and self.projection_dim < 1:
            fail("projection_dim",
                 f"must be None or >= 1, got {self.projection_dim}")
        if self.batch_size < 1:
            fail("batch_size", f"must be >= 1, got {self.batch_size}")
