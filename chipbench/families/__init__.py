"""Model families: plain forward, seeded weights and data, FLOP count."""
