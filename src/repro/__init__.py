"""repro: cuSZ (PACT'20) reproduced as a TPU-native JAX compression
substrate inside a multi-pod LM training/serving framework."""

__version__ = "1.0.0"
