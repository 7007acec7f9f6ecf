"""Unified JAX model zoo for the assigned architectures."""
from .model import ArchConfig, MoECfg, SSMCfg, decode_step, init, \
    init_cache, params_count, prefill, train_loss, train_loss_and_stats

__all__ = ["ArchConfig", "MoECfg", "SSMCfg", "decode_step", "init",
           "init_cache", "params_count", "prefill", "train_loss",
           "train_loss_and_stats"]
