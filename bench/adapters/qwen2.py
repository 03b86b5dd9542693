"""How the benchmark drives the program's serving engine for a Qwen2
configuration: the program's own config object, the benchmark's weights
laid out as the program keeps them, and the engine the cell runs."""

from __future__ import annotations

import jax

from repro.configs.base import ArchConfig
from repro.serve import ServeEngine


def arch_config(cfg: dict) -> ArchConfig:
    """The program's config object, every number from the file."""
    return ArchConfig(
        name=f"qwen2-{cfg['num_hidden_layers']}l-v{cfg['vocab_size']}",
        family="dense", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qkv_bias=True, rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"])


def program_params(w: dict) -> dict:
    """The reference's weights in the program's tree: one scanned unit
    per layer, each holding ``block0``."""
    lw = w["layers"]
    return {
        "embed": {"table": w["embed"]},
        "final_norm": {"scale": w["final_norm"]},
        "lm_head": {"w": w["lm_head"]},
        "layers": {"block0": {
            "norm1": {"scale": lw["norm1"]},
            "norm2": {"scale": lw["norm2"]},
            "attn": {"wq": lw["wq"], "wk": lw["wk"], "wv": lw["wv"],
                     "wo": lw["wo"], "q_bias": lw["bq"],
                     "k_bias": lw["bk"], "v_bias": lw["bv"]},
            "mlp": {"w_gate": lw["w_gate"], "w_up": lw["w_up"],
                    "w_down": lw["w_down"]},
        }},
    }


def max_len(traffic: dict) -> int:
    return traffic["prompt"]["max"] + traffic["output"]["max"]


def engine(cfg: dict, traffic: dict, params, *, sample=None) -> ServeEngine:
    """The engine the cell serves from: the configuration's path, the
    mix's slots, block size and pool."""
    return ServeEngine(
        arch_config(cfg), params, batch=traffic["slots"],
        max_len=max_len(traffic), kv_block_size=traffic["kv_block_size"],
        kv_blocks=traffic["kv_blocks"], sample=sample, **cfg["engine"])


def abstract_engine(cfg: dict, traffic: dict):
    """The engine built over abstract weights, for a compile without the
    chip; returns it with the weights' shapes."""
    from models import qwen2 as ref
    shapes = jax.eval_shape(lambda: program_params(
        ref._weights(cfg, ref.seed_key(0))))
    return engine(cfg, traffic, shapes), shapes
