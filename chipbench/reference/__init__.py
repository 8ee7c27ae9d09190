"""Plain references: ``jax.numpy`` in float32 at the highest matmul precision.

Nothing here imports the program. Each module follows the published
description of its architecture and notes each departure the program makes.
"""
