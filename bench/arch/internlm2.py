"""`model_type` internlm2: the dense decoder (bench/arch/dense.py)."""
from bench.arch.dense import *  # noqa: F401,F403
