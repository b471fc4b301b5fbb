"""The yardstick: what every cell's run shares and no later PR edits."""
