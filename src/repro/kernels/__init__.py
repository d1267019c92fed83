"""Pallas TPU kernels for the framework's compute hot spots.

  flash_attention — causal+GQA online-softmax attention (train/prefill)
  ssd             — Mamba-2 SSD chunk scan (ssm/hybrid archs)
  skyline         — bulk AREPAS skyline simulation (TASQ data augmentation)
  cluster_step    — fused cluster epoch step + elastic resize (replay loop)

Each kernel has a pure-jnp reference and a wrapper in ops.py. On a TPU the
kernels compile through Mosaic; on the CPU, interpret=True executes the
kernel body for the tests.
"""
from repro.kernels.ops import (arepas_runtimes, cluster_epoch_step,
                               cluster_resize_step, flash_attention,
                               ssd_scan)

__all__ = ["arepas_runtimes", "cluster_epoch_step", "cluster_resize_step",
           "flash_attention", "ssd_scan"]
