"""Mixture-of-experts decoder with grouped-query attention (DeepSeekMoE):
``first_k_dense_replace`` dense layers, then layers whose MLP is
``n_routed_experts`` routed experts of width ``moe_intermediate_size``,
``num_experts_per_tok`` of them per token, beside ``n_shared_experts``
shared ones.  How its configuration file maps onto the program, its adapter
targets' shapes, and the operations and bytes of its served work; the
routed experts run as one named kernel, ``moe_experts``, once per expert
layer in each decode step."""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from bench.costs import BF16, adapter_layer_bytes, adapter_token_flops

EXPERTS = "moe_experts"


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
        moe=MoEConfig(num_experts=conf["n_routed_experts"],
                      top_k=conf["num_experts_per_tok"],
                      num_shared=conf["n_shared_experts"],
                      d_ff_expert=conf["moe_intermediate_size"],
                      first_k_dense=conf["first_k_dense_replace"],
                      d_ff_dense=conf["intermediate_size"]),
        lora=LoRAConfig(rank=ad["rank"], targets=tuple(ad["targets"])))


def adapter_dims(conf: Dict) -> Dict[str, Tuple[int, int]]:
    a = arch(conf)
    return {t: a.target_dims(t) for t in ("q", "k", "v", "o")}


def arch(conf: Dict) -> "Arch":
    H = conf["num_attention_heads"]
    return Arch(d=conf["hidden_size"], H=H, Kv=conf["num_key_value_heads"],
                hd=conf["hidden_size"] // H, dff=conf["intermediate_size"],
                f=conf["moe_intermediate_size"], E=conf["n_routed_experts"],
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
    E: int
    k: int
    shared: int
    fk: int
    L: int
    vocab: int

    def target_dims(self, t: str) -> Tuple[int, int]:
        qd, kvd = self.H * self.hd, self.Kv * self.hd
        return {"q": (self.d, qd), "k": (self.d, kvd), "v": (self.d, kvd),
                "o": (qd, self.d)}[t]

    @property
    def attn_params(self) -> int:
        return 2 * self.d * self.H * self.hd + 2 * self.d * self.Kv * self.hd

    def _matmul_params(self, experts: int) -> int:
        """Matrix parameters of every layer, with ``experts`` routed
        experts counted in each expert layer (the router included)."""
        dense = self.attn_params + 3 * self.d * self.dff
        moe = self.attn_params + self.d * self.E \
            + 3 * self.d * self.f * (experts + self.shared)
        return self.fk * dense + (self.L - self.fk) * moe

    def _token_flops(self, ad: Dict) -> int:
        return 2 * self._matmul_params(self.k) + self.L * adapter_token_flops(
            self, ad, ad["targets"])

    def decode_step_flops(self, ad: Dict, kv_lens: Sequence[int]) -> int:
        attn = sum(self.L * 4 * self.H * self.hd * kv for kv in kv_lens)
        return len(kv_lens) * (self._token_flops(ad)
                               + 2 * self.d * self.vocab) + attn

    def prefill_flops(self, ad: Dict, prompt_len: int) -> int:
        P = prompt_len
        attn = self.L * 4 * self.H * self.hd * P * (P + 1) // 2
        return P * self._token_flops(ad) + attn + 2 * self.d * self.vocab

    def decode_step_bytes(self, ad: Dict, kv_lens: Sequence[int],
                          ids: Sequence[int]) -> int:
        weights = (self._matmul_params(self.E) + self.d * self.vocab) * BF16
        kv = self.L * sum(2 * kv * self.Kv * self.hd * BF16 for kv in kv_lens)
        return weights + kv + self.L * adapter_layer_bytes(
            self, ad, ad["targets"], ids)

    def kernel_calls(self, kernel: str, ad: Dict, kv_lens: Sequence[int],
                     ids: Sequence[int]) -> Optional[List[Tuple[int, int, int]]]:
        if kernel != EXPERTS:
            return None
        B = len(kv_lens)
        flops = B * self.k * 2 * 3 * self.d * self.f
        nbytes = (self.E * 3 * self.d * self.f + 2 * B * self.d) * BF16
        return [(flops, nbytes, self.L - self.fk)]
