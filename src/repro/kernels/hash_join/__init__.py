from .ref import (direct_slots, hash_build, hash_keys, hash_keys_np,
                  hashed_slots, probe_lengths_np)

__all__ = ["direct_slots", "hash_build", "hash_keys", "hash_keys_np",
           "hashed_slots", "probe_lengths_np"]
