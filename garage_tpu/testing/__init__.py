"""Test/bench harnesses that ship in-tree: fault injection and WAN
emulation.  These are not daemon code paths — they drive the product
from outside — but they live in the package so tests, scripts/ and
operator tooling share one implementation (the reference keeps its
equivalents in tests/common/ and external mknet configs)."""
