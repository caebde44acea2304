"""Plain float32 references of the benchmark's configurations.

They import nothing of the program: each is written from the published
description plus the departures its configuration file lists, in
straightforward ``jax.numpy`` with every matmul at ``HIGHEST`` precision.
"""
