"""Plain float32 references, one module per architecture. A configuration
file names its module under ``reference``; each module has
``score(model, seed, tokens, positions, probes, quant=False)``."""
