"""``pytest benchmark/`` runs on the CPU, by hand: these tests are the
benchmark's own checks and are not part of ``tests/``."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
