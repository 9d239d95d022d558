"""repro — cost-based operator-fusion-plan optimization for JAX/TPU.

Reimplementation of Boehm et al., "On Optimizing Operator Fusion Plans for
Large-Scale Machine Learning in SystemML" (PVLDB 2018), embedded in a
multi-pod JAX training/serving framework.
"""

__version__ = "0.1.0"
