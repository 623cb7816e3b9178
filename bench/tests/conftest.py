import os

# The benchmark's CPU tests run the program under the Pallas interpreter.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
