import os
import sys

import jax

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

# the repo's suite runs with implicit rank promotion disabled; so does
# the benchmark's
jax.config.update("jax_numpy_rank_promotion", "raise")
