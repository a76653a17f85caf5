"""repro — GLB (lifeline-based global load balancing) as a JAX/TPU framework.

The paper's contribution lives in repro.core; its workloads in
repro.problems; the LM training/serving stack that hosts the technique as a
first-class feature (MoE expert placement, serving-replica balancing) in
the sibling subpackages. See DESIGN.md at the repo root.
"""
__version__ = "1.0.0"
