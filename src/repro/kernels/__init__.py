"""Pallas TPU kernels for FedAttn compute hot-spots.

Each kernel ships three artifacts:
  <name>.py  pl.pallas_call + BlockSpec implementation (TPU target)
  ops.py     public wrappers with backend dispatch
  ref.py     pure-jnp oracles used for validation and as CPU fallback
"""
