"""Pallas TPU kernels (compiled on a TPU, Mosaic-interpreted elsewhere).

topo_score       — the paper's candidate-sourcing hot loop as bitmask lane math
flash_attention  — blocked causal/SWA GQA attention (train/prefill hot spot)
"""
from . import flash_attention, ref, topo_score

__all__ = ["flash_attention", "ref", "topo_score"]
