"""Integer forward/backward fine-tuning reproduction (JAX + Pallas)."""
