"""Mixture-of-experts decoder with grouped-query attention (DeepSeekMoE),
as one chip of an expert-parallel deployment: ``first_k_dense_replace``
dense layers, then layers whose MLP is a router over
``program.router_experts`` routed experts (``num_experts_per_tok`` of them
per token), the ``n_routed_experts`` of them held on this chip (from
``program.expert_offset``), and ``n_shared_experts`` shared ones.  How its
configuration file maps onto the program, its adapter targets' shapes, and
the operations and bytes of its served work.

Counts are the least the work needs, as in `bench.archs.dense_gqa`: the
routed experts count the pairs routed here, on average ``k * held /
router`` per token (the telemetry's counters give a step's own).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from bench import spec
from bench.costs import BF16, adapter_layer_bytes, adapter_token_flops

dense = spec.arch("dense_gqa")
# the routed experts held here, in scope ``moe/experts`` of the decode step
HELD_EXPERTS = "held_experts"


def model_config(conf: Dict, traffic: Dict):
    from repro.configs.base import LoRAConfig, ModelConfig, MoEConfig

    prog = conf["program"]
    ad = traffic["adapters"]
    return ModelConfig(
        name=conf["name"], family=prog["family"],
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["hidden_size"] // conf["num_attention_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        qk_norm=prog["qk_norm"], rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        moe=MoEConfig(num_experts=prog["router_experts"],
                      top_k=conf["num_experts_per_tok"],
                      num_shared=conf["n_shared_experts"],
                      d_ff_expert=conf["moe_intermediate_size"],
                      first_k_dense=conf["first_k_dense_replace"],
                      d_ff_dense=conf["intermediate_size"],
                      norm_topk_prob=bool(conf["norm_topk_prob"]),
                      held_experts=conf["n_routed_experts"],
                      expert_offset=prog["expert_offset"]),
        lora=LoRAConfig(rank=ad["rank"], targets=tuple(ad["targets"])))


def adapter_dims(conf: Dict) -> Dict[str, Tuple[int, int]]:
    a = arch(conf)
    return {t: a.target_dims(t) for t in ("q", "k", "v", "o")}


def arch(conf: Dict) -> "Arch":
    H = conf["num_attention_heads"]
    return Arch(d=conf["hidden_size"], H=H, Kv=conf["num_key_value_heads"],
                hd=conf["hidden_size"] // H, dff=conf["intermediate_size"],
                f=conf["moe_intermediate_size"], E=conf["n_routed_experts"],
                router=conf["program"]["router_experts"],
                k=conf["num_experts_per_tok"], shared=conf["n_shared_experts"],
                fk=conf["first_k_dense_replace"],
                L=conf["num_hidden_layers"], vocab=conf["vocab_size"])


@dataclasses.dataclass(frozen=True)
class Arch:
    d: int
    H: int
    Kv: int
    hd: int
    dff: int
    f: int
    E: int          # routed experts held here
    router: int     # routed experts the router scores
    k: int
    shared: int
    fk: int
    L: int
    vocab: int

    @property
    def attention(self):
        """The attention layers' costs: those of a dense decoder of the
        same attention (its fused decode kernel's calls)."""
        return dense.Arch(d=self.d, H=self.H, Kv=self.Kv, hd=self.hd,
                          dff=self.dff, L=self.L, vocab=self.vocab)

    def target_dims(self, t: str) -> Tuple[int, int]:
        return self.attention.target_dims(t)

    @property
    def attn_params(self) -> int:
        return 2 * self.d * self.H * self.hd + 2 * self.d * self.Kv * self.hd

    def _matmul_params(self, experts: float) -> float:
        """Matrix parameters of every layer, with ``experts`` routed
        experts counted in each expert layer (the router included)."""
        dense_l = self.attn_params + 3 * self.d * self.dff
        moe_l = self.attn_params + self.d * self.router \
            + 3 * self.d * self.f * (experts + self.shared)
        return self.fk * dense_l + (self.L - self.fk) * moe_l

    def _token_flops(self, ad: Dict) -> float:
        """Per token, with the pairs routed to the experts held here."""
        routed_here = self.k * self.E / self.router
        return 2 * self._matmul_params(routed_here) + self.L * \
            adapter_token_flops(self, ad, ad["targets"])

    def decode_step_flops(self, ad: Dict, kv_lens: Sequence[int]) -> float:
        attn = sum(self.L * 4 * self.H * self.hd * kv for kv in kv_lens)
        return len(kv_lens) * (self._token_flops(ad)
                               + 2 * self.d * self.vocab) + attn

    def prefill_flops(self, ad: Dict, prompt_len: int) -> float:
        P = prompt_len
        attn = self.L * 4 * self.H * self.hd * P * (P + 1) // 2
        return P * self._token_flops(ad) + attn + 2 * self.d * self.vocab

    def decode_step_bytes(self, ad: Dict, kv_lens: Sequence[int],
                          ids: Sequence[int]) -> int:
        """Every weight held here once (the held experts', the shared
        ones, the router), the K/V of every attended token and the batch's
        adapter factors."""
        weights = (self._matmul_params(self.E) + self.d * self.vocab) * BF16
        kv = self.L * sum(2 * kv * self.Kv * self.hd * BF16 for kv in kv_lens)
        return weights + kv + self.L * adapter_layer_bytes(
            self, ad, ad["targets"], ids)

    def experts_least(self, routed: float, used: int) -> Tuple[float, int]:
        """(FLOPs, bytes) the routed experts need for ``routed`` (token,
        expert) pairs on ``used`` held experts: each pair's gated FFN, each
        used expert's weights read once, each pair's activation in and
        out."""
        flops = routed * 2 * 3 * self.d * self.f
        nbytes = (used * 3 * self.d * self.f + routed * 2 * self.d) * BF16
        return flops, nbytes

    def kernel_calls(self, kernel: str, ad: Dict, kv_lens: Sequence[int],
                     ids: Sequence[int]) -> Optional[List[Tuple[int, int, int]]]:
        """The fused decode kernel's calls, one a layer (as the dense
        decoder's), and the held experts', one an expert layer, for a
        router that spreads the batch evenly (``held_experts_roofline``
        reads the program's own counts)."""
        if kernel == HELD_EXPERTS:
            routed = len(kv_lens) * self.k * self.E / self.router
            return [self.experts_least(routed, self.E) + (self.L - self.fk,)]
        return self.attention.kernel_calls(kernel, ad, kv_lens, ids)
