# ops.py: the engine's drain tick and its packing helpers (jnp).
# ssd_scan.py: the LM stack's Pallas SSD scan with its jnp oracle; the
# simulator does not import it.
