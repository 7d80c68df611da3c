"""One module per kind of configuration, named by the configuration's
``kind`` key: it sets up, runs the measured window and checks what the
window produced."""
