"""Traffic kinds: one general driver per kind of traffic mix."""
