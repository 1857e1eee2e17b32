"""Four host devices for the checks of training cells, set before JAX
starts: the training checks run a 2x2 mesh on the CPU."""
import os

os.environ["XLA_FLAGS"] = " ".join(
    [os.environ.get("XLA_FLAGS", ""),
     "--xla_force_host_platform_device_count=4"]).strip()
