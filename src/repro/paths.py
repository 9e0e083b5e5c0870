"""Where the system keeps its run-time state: inside its checkout, in
directories ``.gitignore`` lists (``.jax_cache/`` for the persistent
compilation cache, ``.work/`` for weights)."""
from pathlib import Path

# src/repro/paths.py -> the checkout root
REPO_ROOT = Path(__file__).resolve().parents[2]
