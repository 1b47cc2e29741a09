"""The benchmark's own library: everything `run.py` needs that is not one
configuration's, one traffic mix's, one query class's, one loop kind's or
one per-layer metric's. Those live in directories of their own beside this
one and are found by name (`registry.py`)."""
