"""The harness's tests run every cell on the CPU. A cell that asks for
four chips needs four devices there, so the CPU backend is given four
(the one-chip cells use the first): set before JAX starts."""

import os

if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=4"
                               ).strip()
