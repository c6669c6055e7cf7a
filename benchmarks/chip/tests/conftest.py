import os
import sys

import jax

# four CPU devices for the training cell's data mesh; set before any test
# touches a backend
jax.config.update("jax_num_cpu_devices", 4)

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import cells  # noqa: E402,F401  (puts the harness and src/ on the path)
