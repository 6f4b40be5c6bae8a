"""The yardstick's own code: nothing here imports from `ray_tpu`."""
