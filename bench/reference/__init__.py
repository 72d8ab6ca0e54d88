"""Plain references that decide ``correct``.

They import nothing of the program under test and take nothing it made:
neighbour lists come from a k-d tree over the positions, energies from
straightforward ``jax.numpy`` at the highest matmul precision, forces and
effective fields from autodiff of that energy, and the integrator is a
plain transcription of the coupled OBABO splitting.
"""
