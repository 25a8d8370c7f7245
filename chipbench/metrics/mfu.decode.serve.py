"""``mfu.decode`` (the decode step's share of the chip's peak by its
bounding roof) in cells where it moves ``serve_tokens_per_s``."""
import harness

read = harness.load_module(harness.HERE / "metrics" / "mfu.decode.py").read
