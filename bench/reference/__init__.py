"""Plain float32 references, one per model family."""
