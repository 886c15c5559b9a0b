"""Plain references of every configuration's architecture, kept with the
yardstick so that no later PR can move them."""
