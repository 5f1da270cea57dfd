"""Operations and bytes the served model needs, from its shapes alone.

Counted for the work itself, whichever implementation runs it: a routed
expert layer does top-k experts' work per token, a decode row attends
to its own context, padding and empty slots count for nothing.  An
operation is a multiply or an add (a multiply-accumulate is two).

A reference module names its architecture's work class (``WORK``); the
metric readers (``bench/readers.py``) call nothing of it but ``Work``'s
methods.  A work class imports nothing of the program.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Protocol


class Work(Protocol):
    """What the readers ask of an architecture's work class."""

    @classmethod
    def of(cls, model: dict) -> "Work":
        """The counts of the configuration file's ``model`` sizes."""

    def gemm_ops(self) -> float:
        """int8 operations of one token's layer GEMMs."""

    def attn_ops(self, context: float) -> float:
        """bf16/f32 operations of one token's attention over `context`
        keys."""

    def head_ops(self) -> float:
        """bf16/f32 operations of one token's LM head (and router)."""

    def decode_gemm_ops(self, rows: int) -> float:
        """int8 operations of a decode call's layer GEMMs over `rows`."""

    def decode_gemm_bytes(self, rows: int) -> float:
        """Bytes a decode call's layer GEMMs must move for `rows`."""

    def decode_head_ops(self, rows: int) -> float:
        """bf16 operations of a decode call's LM head over `rows`."""

    def decode_head_bytes(self, rows: int) -> float:
        """Bytes a decode call's LM head must move for `rows`."""

    def decode_attn_ops(self, rows: int, context: float) -> float:
        """bf16/f32 operations of a decode call's attention over `rows`
        at mean `context`."""

    def decode_attn_bytes(self, rows: int, context: float) -> float:
        """Bytes a decode call's attention must move over `rows` at mean
        `context`: the cache it reads and the new token's entry it
        writes."""


@dataclass(frozen=True)
class Shapes:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    experts: int = 0
    top_k: int = 0

    @classmethod
    def of(cls, model: dict) -> "Shapes":
        return cls(model["num_hidden_layers"], model["hidden_size"],
                   model["num_attention_heads"],
                   model["num_key_value_heads"], model["head_dim"],
                   model["intermediate_size"], model["vocab_size"],
                   model.get("num_experts", 0),
                   model.get("num_experts_per_tok", 0))

    # -- parameters ------------------------------------------------------
    def attn_params(self) -> int:
        """Per layer: q, k, v and output projections."""
        return self.d * self.head_dim * (2 * self.heads + 2 * self.kv_heads)

    def ffn_params(self) -> int:
        """Per layer, every expert (SwiGLU: gate, up, down)."""
        return 3 * self.d * self.ff * max(self.experts, 1)

    def ffn_active_params(self) -> int:
        """Per layer, the experts one token runs."""
        return 3 * self.d * self.ff * (self.top_k if self.experts else 1)

    def router_params(self) -> int:
        return self.d * self.experts

    def total_params(self, tied: bool) -> int:
        layer = self.attn_params() + self.ffn_params() + self.router_params()
        emb = self.vocab * self.d * (1 if tied else 2)
        return self.layers * layer + emb

    def active_params(self) -> int:
        """Parameters one token's forward pass multiplies: its layers and
        the LM head (the embedding row it looks up is no GEMM)."""
        layer = (self.attn_params() + self.ffn_active_params()
                 + self.router_params())
        return self.layers * layer + self.vocab * self.d

    # -- work per token --------------------------------------------------
    def gemm_ops(self) -> float:
        """int8 MAC operations of one token's layer GEMMs."""
        return 2.0 * self.layers * (self.attn_params()
                                    + self.ffn_active_params())

    def attn_ops(self, context: float) -> float:
        """bf16/f32 operations of one token's attention over `context`
        keys (scores and the weighted sum) in every layer."""
        return 4.0 * self.layers * self.heads * self.head_dim * context

    def head_ops(self) -> float:
        """The LM head (bf16) and the router, per token."""
        return 2.0 * (self.vocab * self.d + self.layers * self.router_params())

    # -- one decode call -------------------------------------------------
    def decode_gemm_bytes(self, rows: int) -> float:
        """Bytes a decode call's layer GEMMs must move: every int8 weight
        it touches once (all experts, when rows * top_k reach them all),
        its f32 scales, and bf16 activations in and out."""
        if self.experts:
            touched = min(self.experts, rows * self.top_k)
            ffn = 3 * self.d * self.ff * touched
        else:
            ffn = self.ffn_params()
        weights = self.layers * (self.attn_params() + ffn)
        acts = 2 * rows * self.layers * (
            self.d * 4 + self.heads * self.head_dim * 2
            + 2 * self.kv_heads * self.head_dim
            + 3 * self.ff * (self.top_k if self.experts else 1))
        return float(weights + acts)

    def decode_gemm_ops(self, rows: int) -> float:
        return rows * self.gemm_ops()

    def decode_head_ops(self, rows: int) -> float:
        """The bf16 LM head over `rows`."""
        return rows * 2.0 * self.vocab * self.d

    def decode_head_bytes(self, rows: int) -> float:
        """The bf16 LM head read once, whatever the rows."""
        return float(self.vocab * self.d * 2)

    def decode_attn_ops(self, rows: int, context: float) -> float:
        return rows * self.attn_ops(context)

    def decode_attn_bytes(self, rows: int, context: float) -> float:
        """Every row's cached K and V read once in bf16, and the new
        token's K and V written, in every layer."""
        return float(rows * self.layers * (context + 1) * 2 * self.kv_heads
                     * self.head_dim * 2)
