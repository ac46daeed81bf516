"""Work of a training step (matmul and attention shapes) per model family."""
