"""The plain reference: what each field of a log line is, written from
the documented semantics of Apache httpd's ``mod_log_config``, NGINX's
``log_format``, and the dissectors of the upstream logparser
(``HttpFirstLineDissector``, ``HttpUriDissector``, ``TimeStampDissector``)
that the program re-implements.  It imports nothing of the program and
takes nothing the program made.

One regular expression per LogFormat, one function per field type, one
line at a time.  ``parse_line`` returns the field dict of a line the
format accepts and ``None`` for one it rejects.
"""
import calendar
import re

MONTHS = {m: i for i, m in enumerate(
    ("jan", "feb", "mar", "apr", "may", "jun",
     "jul", "aug", "sep", "oct", "nov", "dec"), 1)}

NO_SPACE = r"[^\s]*"
ANY = r".*?"
CLF_NUMBER = r"[0-9]+|-"
NUMBER = r"[0-9]+"
TIME = (r"[0-3][0-9]/[a-zA-Z]{3}/[1-9][0-9]{3}:[0-9]{2}:[0-9]{2}:[0-9]{2} "
        r"[+|\-][0-9]{4}")

# directive -> (the field it carries, the bytes it may hold)
APACHE = {
    "%h": ("connection.client.host", NO_SPACE),
    "%l": ("connection.client.logname", CLF_NUMBER),
    "%u": ("connection.client.user", NO_SPACE),
    "%t": ("request.receive.time", r"\[(?:" + TIME + r")\]"),
    "%r": ("request.firstline", ANY),
    "%>s": ("request.status.last", NO_SPACE),
    "%b": ("response.body.bytes", CLF_NUMBER),
    "%{Referer}i": ("request.referer", ANY),
    "%{User-agent}i": ("request.user-agent", ANY),
}
NGINX = {
    "$remote_addr": ("connection.client.host", NO_SPACE),
    "$remote_user": ("connection.client.user", NO_SPACE),
    "$time_local": ("request.receive.time", TIME),
    "$request": ("request.firstline", ANY),
    "$status": ("request.status.last", NUMBER),
    "$body_bytes_sent": ("response.body.bytes", NUMBER),
    "$http_referer": ("request.referer", ANY),
    "$http_user_agent": ("request.user-agent", ANY),
}
NAMED_FORMATS = {
    "combined": '%h %l %u %t "%r" %>s %b "%{Referer}i" "%{User-agent}i"',
}

FIRSTLINE = re.compile(r"^([a-zA-Z\-_]+) (.*) (HTTP/[0-9]+\.[0-9]+)$")
FIRSTLINE_CUT = re.compile(r"^([a-zA-Z\-_]+) (.*)$")


def compile_format(log_format):
    """(regex, [field name per group]) for a LogFormat or log_format."""
    fmt = NAMED_FORMATS.get(log_format, log_format)
    table = NGINX if "$" in fmt else APACHE
    keys = sorted(table, key=len, reverse=True)
    pattern = "|".join(re.escape(k) for k in keys)
    out, names, pos = ["^"], [], 0
    for m in re.finditer(pattern, fmt):
        out.append(re.escape(fmt[pos:m.start()]))
        name, rx = table[m.group(0)]
        if m.group(0) == "%t":
            out.append(r"\[(" + TIME + r")\]")
        else:
            out.append("(" + rx + ")")
        names.append(name)
        pos = m.end()
    out.append(re.escape(fmt[pos:]) + "$")
    return re.compile("".join(out)), names


def epoch_millis(stamp):
    """``dd/Mon/yyyy:HH:MM:SS +hhmm`` -> milliseconds since the epoch."""
    day, mon, rest = stamp.split("/", 2)
    year, hh, mm, ss_zone = rest.split(":", 3)
    ss, zone = ss_zone.split(" ")
    secs = calendar.timegm((int(year), MONTHS[mon.lower()], int(day),
                            int(hh), int(mm), int(ss)))
    off = int(zone[1:3]) * 3600 + int(zone[3:5]) * 60
    return (secs - off if zone[0] == "+" else secs + off) * 1000


# -- HttpUriDissector, step by step ---------------------------------------

_ESCAPE_ALWAYS = set(range(0x20)) | {0x7F, 0xFF} | set(b' {}|\\^[]`<>"')
_BAD_PERCENT = re.compile("%([^0-9a-fA-F]|[0-9a-fA-F][^0-9a-fA-F]|.$|$)")


def _encode_unsafe(s):
    out = []
    for b in s.encode("utf-8"):
        out.append("%%%02X" % b if b in _ESCAPE_ALWAYS else chr(b))
    return "".join(out)


def _decode_percent(s):
    """java.net.URI.decode: runs of %XX become bytes, read as UTF-8."""
    out, i, n = [], 0, len(s)
    while i < n:
        if s[i] == "%" and i + 2 < n + 1:
            run = bytearray()
            while i < n and s[i] == "%" and i + 2 < n:
                try:
                    run.append(int(s[i + 1:i + 3], 16))
                except ValueError:
                    break
                i += 3
            if run:
                out.append(run.decode("utf-8", errors="replace"))
                continue
        out.append(s[i])
        i += 1
    return "".join(out)


def _html_unescape(s):
    import html.entities

    s = re.sub(r"&#(?:[xX]([0-9a-fA-F]+)|([0-9]+));",
               lambda m: chr(int(m.group(1), 16) if m.group(1) else
                             int(m.group(2))), s)
    return re.sub(r"&([a-zA-Z][a-zA-Z0-9]*);",
                  lambda m: html.entities.entitydefs.get(m.group(1),
                                                         m.group(0)), s)


def uri_parts(uri):
    """(path, query) of a request URI after the upstream repair chain:
    escape unsafe bytes, make the query start with ``?&``, repair bare
    ``%`` signs, unescape HTML entities, then split like java.net.URI."""
    s = _encode_unsafe(uri)
    if "?" in s or "&" in s:
        s = s.replace("?", "&").replace("&", "?&", 1)
    s = _BAD_PERCENT.sub(r"%25\1", s)
    s = _BAD_PERCENT.sub(r"%25\1", s)
    s = _html_unescape(s)
    if "#" in s:
        raise ValueError("the weblog corpus has no fragments")
    if s.startswith("/"):
        rest = s
    else:
        m = re.match(r"^[A-Za-z][A-Za-z0-9+.\-]*://[^/?]*(.*)$", s)
        if m is None:
            raise ValueError("not a URI the weblog corpus makes: %r" % uri)
        rest = m.group(1)
    path, _, query = rest.partition("?")
    if " " in path or " " in query:
        raise ValueError("space in a repaired URI: %r" % uri)
    return _decode_percent(path), (query if "?" in rest else "")


# -- one line --------------------------------------------------------------

def _null_dash(v):
    return None if v == "-" else v


def field_value(field, raw, firstline):
    """The value of one requested ``TYPE:name`` field."""
    ftype, name = field.split(":", 1)
    if name == "request.firstline.method":
        return firstline[0]
    if name == "request.firstline.uri":
        return firstline[1]
    if name in ("request.firstline.uri.path", "request.firstline.uri.query"):
        if firstline[1] is None:
            return None
        path, query = uri_parts(firstline[1])
        return path if name.endswith("path") else query
    if ftype == "TIME.EPOCH":
        return epoch_millis(raw["request.receive.time"])
    if ftype == "TIME.STAMP":
        return raw["request.receive.time"]
    if ftype == "BYTES":
        v = raw["response.body.bytes"]
        return 0 if v == "-" else int(v)
    return _null_dash(raw[name])


def split_firstline(line):
    if line in ("", "-"):
        return (None, None)
    m = FIRSTLINE.match(line) or FIRSTLINE_CUT.match(line)
    return (m.group(1), m.group(2)) if m else (None, None)


class Reference:
    def __init__(self, log_format, fields):
        self.regex, self.names = compile_format(log_format)
        self.fields = list(fields)

    def parse_line(self, line):
        m = self.regex.match(line)
        if m is None:
            return None
        raw = dict(zip(self.names, m.groups()))
        first = split_firstline(raw["request.firstline"])
        return {f: field_value(f, raw, first) for f in self.fields}

    def valid(self, line):
        return self.regex.match(line) is not None
