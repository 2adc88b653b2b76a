"""Plain float32 reference of a dense decoder, independent of the code
under test: its own weights from the seed, its own forward, loss,
gradients and AdamW."""
